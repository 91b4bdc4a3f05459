"""Declarative kernel registry: the eight Pallas dispatch sites as data.

Each :class:`KernelSpec` names one dispatch site (the public wrapper in
``kernels/ops.py``), its Pallas implementation, its pure-jnp oracle
(``kernels/ref.py``), the tile space the autotuner (``kernels/tune.py``)
may sweep, and the backends it has a *compiled* lowering for.  ``ops.py``
used to hand-write the wrappers; now one generic dispatcher walks this
table, so bench (``benchmarks/kernel_bench.py``), tests, and the tuner can
enumerate every kernel without keeping a parallel list in sync.

Tile settings are kwargs dicts (``{"tile": 256}``, ``{"row_tile": 128}``,
``{"tile_bh": 8, "chunk": 64}``); the FIRST entry of ``tile_space`` is the
do-nothing default (``{}``), which preserves each kernel's built-in tile
constants — the autotuner only ever *narrows* from measured evidence, never
changes untuned behavior.  ``bucket`` maps a concrete call to the pow2
shape bucket the tuner caches winners under (same bucket => same winner, so
chunked/streamed callers at one chunk shape tune exactly once).

``compiled`` lists the hardware backend tags with a real lowering:
everything lowers via Mosaic on TPU and Triton on GPU, EXCEPT ``wkv6``,
whose cross-chunk accumulator lives in a ``pltpu.VMEM`` scratch — a
TPU-only primitive — so on GPU it falls back to the jnp oracle rather than
pretending to compile.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

from repro.kernels import ref as _ref
from repro.kernels.bank_sched import bank_sched as _sched_pallas
from repro.kernels.bit_signature import bit_signature as _bs_pallas
from repro.kernels.fail_prob import fail_prob as _fp_pallas
from repro.kernels.rc_transient import rc_transient as _rc_pallas
from repro.kernels.secded import encode_checks as _enc_pallas
from repro.kernels.secded import syndrome as _syn_pallas
from repro.kernels.shuffle import apply_shuffle as _shuf_pallas
from repro.kernels.wkv6 import wkv6 as _wkv6_pallas

#: hardware backend tags with real (non-interpret) lowerings
GPU = "gpu-triton"
TPU = "tpu-mosaic"


def _lead_dim(args, kw) -> int:
    """Default shape bucket: the leading (tiled) axis of the first array."""
    return int(args[0].shape[0])


@dataclass(frozen=True)
class KernelSpec:
    """One dispatch site: implementations, tile space, and lowering support.

    ``pallas`` must accept ``interpret=`` plus the tile kwargs named in
    ``tile_space``; ``oracle`` takes the same positional/keyword args minus
    those.  ``batch_in_axes`` documents the vmap rule of the ``*_batch``
    wrapper riding this site (None = the site has no batch wrapper).
    """
    name: str
    pallas: Callable
    tile_space: tuple[dict[str, Any], ...] = ({},)
    bucket: Callable = _lead_dim
    compiled: tuple[str, ...] = (GPU, TPU)
    batch_in_axes: tuple | None = None

    @property
    def oracle(self) -> Callable:
        """The jnp oracle, resolved on ``kernels/ref.py`` at CALL time —
        dispatch-site names equal ref function names by construction.  Late
        binding keeps ``monkeypatch.setattr(ref, name, ...)`` visible to
        dispatch, exactly as the old hand-written wrappers were."""
        return getattr(_ref, self.name)


def _fail_prob_bucket(args, kw) -> int:
    # (row_src (R,), d_mat (M,), coeffs): R is the tiled axis, M the grid
    return int(args[0].shape[0])


def _wkv6_bucket(args, kw) -> int:
    # (B, S, H, dh): the merged BH axis tiles, S chunks — bucket on B*H*S
    r = args[0]
    return int(r.shape[0] * r.shape[2] * r.shape[1])


REGISTRY: dict[str, KernelSpec] = {s.name: s for s in (
    KernelSpec(
        "secded_encode", _enc_pallas,
        tile_space=({}, {"tile": 128}, {"tile": 256}, {"tile": 1024})),
    KernelSpec(
        "secded_syndrome", _syn_pallas,
        tile_space=({}, {"tile": 128}, {"tile": 256}, {"tile": 1024})),
    KernelSpec(
        "fail_prob", _fp_pallas,
        tile_space=({}, {"row_tile": 64}, {"row_tile": 128},
                    {"row_tile": 256}),
        bucket=_fail_prob_bucket, batch_in_axes=(0, None, 0)),
    KernelSpec(
        "bit_signature", _bs_pallas,
        tile_space=({}, {"tile": 64}, {"tile": 128}, {"tile": 512})),
    KernelSpec(
        "bank_sched", _sched_pallas,
        # rank-1 queue blocks: Mosaic takes the whole axis or a multiple
        # of 128 lanes
        tile_space=({}, {"q_tile": 128}, {"q_tile": 256})),
    KernelSpec(
        "diva_shuffle", _shuf_pallas,
        tile_space=({}, {"tile": 64}, {"tile": 128}, {"tile": 512})),
    KernelSpec(
        "rc_transient", _rc_pallas,
        tile_space=({}, {"tile": 32}, {"tile": 64}, {"tile": 256})),
    KernelSpec(
        "wkv6", _wkv6_pallas,
        tile_space=({}, {"tile_bh": 4}, {"tile_bh": 16},
                    {"tile_bh": 8, "chunk": 128}),
        bucket=_wkv6_bucket, compiled=(TPU,)),
)}

#: the eight dispatch-site names, in registry order (bench/tests iterate this)
KERNEL_NAMES: tuple[str, ...] = tuple(REGISTRY)
