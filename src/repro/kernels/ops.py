"""Backend-real dispatch for the Pallas kernels.

One authority decides where kernels run: :func:`backend_tag`, which resolves
to exactly one of

=====================  =======================================================
``cpu-ref``            jnp oracles (``kernels/ref.py``).  The CPU *default*:
                       interpret-mode Pallas is ~30x slower than the oracle
                       graphs at population scale, so CPU pays for the fast
                       route, not the validator.
``cpu-pallas-interpret``  Pallas kernels under ``interpret=True`` — the
                       semantics-validation route (one CI leg pins this).
``gpu-triton``         Pallas lowered through Triton (compiled).
``tpu-mosaic``         Pallas lowered through Mosaic (compiled).
=====================  =======================================================

Resolution order: an active :func:`force_backend` context beats
``REPRO_FORCE_REF=1`` (-> ``<plat>-ref``) beats ``REPRO_BACKEND=<tag>``
beats the platform default (tpu -> tpu-mosaic, gpu -> gpu-triton, cpu ->
cpu-ref).  This replaces the old ``interpret_mode()`` heuristic, which
special-cased only TPU — a GPU host silently ran every kernel interpreted.
``use_pallas()`` / ``interpret_mode()`` survive as *derived* views for the
tile heuristics in substrate/discovery.

The eight public wrappers are generated from ``kernels/registry.py`` by one
dispatcher: route to the oracle, or to the Pallas impl with tile kwargs from
the measured autotuner (``kernels/tune.py``).  Public signatures are
unchanged; the ``pallas=None`` convention still resolves the backend at
trace time, and jitted callers still pass the resolved bool as a static
cache key (the ``substrate._shuffling_jit`` convention).  A kernel with no
compiled lowering on the current hardware (``wkv6`` on GPU: its cross-chunk
state is TPU-only VMEM scratch) routes to its oracle rather than silently
interpreting.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import fail_prob as _fail_prob_mod
from repro.kernels import tune as _tune
from repro.kernels.registry import GPU, KERNEL_NAMES, REGISTRY, TPU
from repro.obs import REGISTRY as _OBS_REGISTRY

# Kernel dispatch accounting (obs layer, ARCHITECTURE 3h).  The Python in
# these wrappers only runs while JAX is TRACING (jit/vmap callers replay the
# compiled program without re-entering it), so this counter counts kernel
# TRACES — i.e. lowerings through each dispatch site — not executions.  The
# backend label is the resolved tag of the route that actually lowered
# (``<plat>-ref`` when the oracle graph ran, even if the ambient tag was a
# kernel route that fell back).
_KERNEL_TRACES = _OBS_REGISTRY.counter(
    "repro_kernel_traces_total",
    "kernel dispatch traces by (kernel, backend tag); counts lowerings, "
    "not executions",
    labelnames=("kernel", "backend"))
# lowerings that asked a kernel for its row sums (``reduce_rows``) instead
# of its cell grid: shows that a caller's reduction moved into the kernel
_ROW_REDUCE = _OBS_REGISTRY.counter(
    "repro_kernel_row_reduce_total",
    "kernel dispatch traces in the row-sum output mode by (kernel, backend "
    "tag); counts lowerings, not executions",
    labelnames=("kernel", "backend"))

_COMPILED_TAGS = (GPU, TPU)
_FORCED: list[str] = []  # force_backend stack (innermost last)


def _platform() -> str:
    return jax.default_backend()


def valid_tags(platform: str | None = None) -> tuple[str, ...]:
    """The tags accepted on ``platform`` (default: the current one)."""
    plat = platform or _platform()
    tags = [f"{plat}-ref", f"{plat}-pallas-interpret"]
    if plat == "gpu":
        tags.append(GPU)
    if plat == "tpu":
        tags.append(TPU)
    return tuple(tags)


def backend_tag() -> str:
    """The single backend authority: which route kernel dispatch takes now.

    Also the tag benchmarks stamp on their rows (``kernel_bench.py`` re-
    exports this), so bench and dispatch can never disagree.
    """
    if _FORCED:
        return _FORCED[-1]
    plat = _platform()
    if os.environ.get("REPRO_FORCE_REF", "0") == "1":
        return f"{plat}-ref"
    env = os.environ.get("REPRO_BACKEND", "")
    if env:
        if env not in valid_tags(plat):
            raise ValueError(
                f"REPRO_BACKEND={env!r} invalid on {plat!r}; "
                f"valid: {valid_tags(plat)}")
        return env
    if plat == "tpu":
        return TPU
    if plat == "gpu":
        return GPU
    return "cpu-ref"


@contextlib.contextmanager
def force_backend(tag: str):
    """Pin ``backend_tag()`` for the dynamic extent — stronger than every
    env var, including ``REPRO_FORCE_REF`` (that is the point: benchmarks
    compare routes regardless of the ambient CI leg).  Compiled callers
    beware: programs traced inside keep their route after exit (the backend
    is a trace-time static), so wrap whole entry-point calls, not fragments.
    """
    if tag not in valid_tags():
        raise ValueError(f"backend tag {tag!r} invalid on {_platform()!r}; "
                         f"valid: {valid_tags()}")
    _FORCED.append(tag)
    try:
        yield
    finally:
        _FORCED.pop()


def use_pallas() -> bool:
    """Derived view: does default dispatch (``pallas=None``) take a Pallas
    route?  False on the oracle tags (``*-ref``)."""
    return not backend_tag().endswith("-ref")


def interpret_mode() -> bool:
    """Derived view: would a Pallas route on this host run interpreted?
    False only on the compiled tags (gpu-triton / tpu-mosaic) — previously
    this special-cased TPU alone, so GPU hosts silently interpreted."""
    return backend_tag() not in _COMPILED_TAGS


def _resolve(spec, pallas: bool | None) -> tuple[str, str]:
    """(route, tag) for one dispatch: route in {"ref", "interpret",
    "compiled"}.  An explicit ``pallas`` bool overrides the tag's ref/kernel
    choice (tests force the kernel on CPU with ``pallas=True``); the tag
    still decides interpret-vs-compiled, and a kernel without a compiled
    lowering here falls back to its oracle."""
    tag = backend_tag()
    plat = tag.split("-", 1)[0]
    if pallas is None:
        pallas = not tag.endswith("-ref")
    if not pallas:
        return "ref", f"{plat}-ref"
    if tag in _COMPILED_TAGS:
        if tag in spec.compiled:
            return "compiled", tag
        return "ref", f"{plat}-ref"
    return "interpret", f"{plat}-pallas-interpret"


def _dispatch(name: str, args: tuple, kw: dict, pallas: bool | None,
              tiles: dict | None = None):
    """The one route for all eight sites: oracle, or Pallas with tile kwargs
    from the explicit override / the autotune cache / the kernel defaults."""
    spec = REGISTRY[name]
    route, tag = _resolve(spec, pallas)
    _KERNEL_TRACES.labels(kernel=name, backend=tag).inc()
    if kw.get("reduce_rows"):
        _ROW_REDUCE.labels(kernel=name, backend=tag).inc()
    if route == "ref":
        return spec.oracle(*args, **kw)
    if tiles is None:
        tiles = _tune.get_tiles(spec, tag, route, args, kw)
    tiles = {k: v for k, v in tiles.items() if v is not None}
    return spec.pallas(*args, interpret=route == "interpret", **tiles, **kw)


# ------------------------------------------------------- public dispatchers

def secded_encode(data_bits, *, tile: int | None = None,
                  pallas: bool | None = None):
    return _dispatch("secded_encode", (data_bits,), {}, pallas,
                     None if tile is None else {"tile": tile})


def secded_syndrome(code_bits, tile: int | None = None, *,
                    pallas: bool | None = None):
    return _dispatch("secded_syndrome", (code_bits,), {}, pallas,
                     None if tile is None else {"tile": tile})


def fail_prob(row_src, d_mat, coeffs, *, cols: int, open_bitline: bool = True,
              voltage: bool = False, retention: bool = False,
              reduce_rows: bool = False, row_tile: int | None = None,
              pallas: bool | None = None):
    """The (M, R, C) grid, or with ``reduce_rows`` its (R,) row sums.
    ``voltage``/``retention`` add the operating point's supply shift and
    retention channel, read from the N_OP_COEFFS-wide row.  ``pallas=None``
    resolves the backend tag at trace time; callers that cache compiled
    programs pass the resolved bool so the backend choice keys their cache
    (the ``substrate._shuffling_jit`` convention)."""
    return _dispatch("fail_prob", (row_src, d_mat, coeffs),
                     dict(cols=cols, open_bitline=open_bitline,
                          voltage=voltage, retention=retention,
                          reduce_rows=reduce_rows), pallas,
                     None if row_tile is None else {"row_tile": row_tile})


def fail_prob_batch(row_src, d_mat, coeffs, *, pallas: bool | None = None,
                    **kw):
    """``fail_prob`` (same keyword arguments) vmapped over a leading
    population (DIMM) axis of ``row_src``/``coeffs`` — the dispatch the
    batched substrate and its sharded routes share (one dispatch site: the
    per-DIMM ``fail_prob``)."""
    if pallas is None:
        pallas = use_pallas()
    fn = functools.partial(fail_prob, pallas=pallas, **kw)
    return jax.vmap(fn, in_axes=REGISTRY["fail_prob"].batch_in_axes)(
        row_src, d_mat, coeffs)


def fail_prob_erf_evals(row_src, d_mat, coeffs, *, cols: int,
                        open_bitline: bool = True, voltage: bool = False,
                        pallas: bool | None = None):
    """(D, 2) int32 per DIMM: the access channel's failure terms that
    ``fail_prob_batch(..., reduce_rows=True)`` on the same arguments
    evaluates and those it skips as exactly 0 or 1, in tiles of one mat and
    one term (``kernels/fail_prob.erf_evals``).  The kernel's route counts
    from the mode table it reads, at the default tile that a traced call
    takes; the oracle's route evaluates every term."""
    route, _ = _resolve(REGISTRY["fail_prob"], pallas)
    fn = functools.partial(_fail_prob_mod.erf_evals, cols=cols,
                           open_bitline=open_bitline, voltage=voltage)
    evals = jax.vmap(fn, in_axes=REGISTRY["fail_prob"].batch_in_axes)(
        row_src, d_mat, coeffs)
    if route == "ref":
        evals = jnp.stack([evals.sum(axis=1), jnp.zeros_like(evals[:, 0])],
                          axis=1)
    return evals


def bit_signature(counts, *, nbits: int, tile: int | None = None,
                  pallas: bool | None = None):
    """(N, R) int32 counts -> (N, nbits) int32 per-bit signature sums.
    ``pallas=None`` resolves the backend at trace time; jitted callers
    (``discovery.recover``) pass the resolved bool as a static cache key,
    per the ``fail_prob`` convention."""
    return _dispatch("bit_signature", (counts,), dict(nbits=nbits), pallas,
                     None if tile is None else {"tile": tile})


def bank_sched(*args, pallas: bool | None = None, q_tile: int | None = None,
               **kw):
    """FR-FCFS candidate scoring + projected service times for one scheduler
    step of the memsim grid (see kernels/bank_sched.py for shapes).
    ``pallas=None`` resolves the backend at trace time; the jitted memsim
    simulators pass the resolved bool as a static cache key, per the
    ``fail_prob`` convention."""
    return _dispatch("bank_sched", args, kw, pallas,
                     None if q_tile is None else {"q_tile": q_tile})


def diva_shuffle(bursts, inverse: bool = False, shuffle: bool = True,
                 perm=None, tile: int | None = None,
                 pallas: bool | None = None):
    return _dispatch("diva_shuffle", (bursts,),
                     dict(inverse=inverse, shuffle=shuffle, perm=perm),
                     pallas, None if tile is None else {"tile": tile})


def rc_transient(row_frac, col_frac, *, tile: int | None = None,
                 pallas: bool | None = None, **kw):
    return _dispatch("rc_transient", (row_frac, col_frac), kw, pallas,
                     None if tile is None else {"tile": tile})


def wkv6(r, k, v, wlog, u, *, tile_bh: int | None = None,
         chunk: int | None = None, pallas: bool | None = None):
    tiles = None
    if tile_bh is not None or chunk is not None:
        tiles = {"tile_bh": tile_bh, "chunk": chunk}
    return _dispatch("wkv6", (r, k, v, wlog, u), {}, pallas, tiles)


__all__ = ["backend_tag", "force_backend", "use_pallas", "interpret_mode",
           "valid_tags", "KERNEL_NAMES", *KERNEL_NAMES,
           "fail_prob_batch", "fail_prob_erf_evals"]
