"""Measured tile autotuner for the kernel registry.

One winner per ``(kernel, backend_tag, shape-bucket, mode)``, where the mode
names the output and channel flags the call sets (``reduce_rows``,
``voltage``, ``retention``): a row-sum call or a two-channel call does other
work per tile than a grid call of the same bucket, so none inherits another's
winner.  The first *concrete* call on a sweep-eligible route times every
candidate in the spec's ``tile_space`` (compile excluded,
best-of-``_TIMING_ITERS``) and caches the fastest setting — an in-process
dict, following flashinfer's cached-workspace idiom, with optional JSON
persistence under ``benchmarks/`` so a tuned trajectory can be replayed
without re-measuring.

Hard rules, in order:

* **Never sweep under a trace.**  The ops wrappers run inside jitted
  programs, where args are Tracers — wall-clock timing there is meaningless
  (and calling back into jit would nest traces).  Tracer args always resolve
  to the cached winner or the default tiles, silently.
* **Sweep only where measurement is the point**: compiled routes
  (gpu-triton / tpu-mosaic) sweep on first concrete call; the CPU interpret
  route only sweeps under ``REPRO_AUTOTUNE=1`` (interpret timing ranks VMEM
  shapes, not hardware — useful for exercising the machinery, not worth
  paying ~10 compile+run cycles per bucket on every CI import).
* **Tiles can't change results.**  Every kernel is tile-invariant by
  construction (pad-to-tile + slice-back over independent rows), so the
  winner affects wall clock only — asserted by the tile-invariance tests in
  ``tests/test_kernels.py``.

Sweeps are recorded through the obs registry (``repro_kernel_tune_total``,
labeled ``kernel``/``backend``) — one inc per sweep, not per candidate.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Any

import jax

from repro.obs import REGISTRY as _OBS_REGISTRY

_TIMING_ITERS = 3

#: the call flags that select another kernel body: part of the cache key
MODE_FLAGS = ("reduce_rows", "voltage", "retention")

#: (kernel, backend_tag, bucket, mode) -> winning tile kwargs
_TUNE_CACHE: dict[tuple[str, str, int, str], dict[str, Any]] = {}

_TUNE_SWEEPS = _OBS_REGISTRY.counter(
    "repro_kernel_tune_total",
    "tile-space autotune sweeps by (kernel, backend); one inc per sweep "
    "(winners are cached per shape bucket)",
    labelnames=("kernel", "backend"))

DEFAULT_CACHE_PATH = (Path(__file__).resolve().parents[3]
                      / "benchmarks" / "TUNE_kernels.json")


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") == "1"


def bucket_pow2(n: int) -> int:
    """Round a tiled-axis extent up to a power of two: the cache granularity.
    Chunked callers hit one bucket per chunk shape, so they tune once."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def _has_tracers(args) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args))


def call_mode(kw) -> str:
    """The cache key's mode: the ``MODE_FLAGS`` a call sets, joined by
    ``+`` (``""`` for none)."""
    return "+".join(f for f in MODE_FLAGS if kw.get(f))


def lookup(kernel: str, backend: str, bucket: int,
           mode: str = "") -> dict[str, Any] | None:
    return _TUNE_CACHE.get((kernel, backend, bucket, mode))


def clear() -> None:
    _TUNE_CACHE.clear()


def _time_once(fn) -> float:
    out = fn()
    jax.block_until_ready(out)  # compile + first run excluded from timing
    best = float("inf")
    for _ in range(_TIMING_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep(spec, call) -> dict[str, Any]:
    """Time every candidate tile setting; return the fastest.  Every entry
    of a tile space must compile (``tests/test_tpu_compile.py``), so a
    refusal here propagates: it is a registry bug, not a skip."""
    best_t, best_tiles = float("inf"), dict(spec.tile_space[0])
    for tiles in spec.tile_space:
        t = _time_once(lambda: call(dict(tiles)))
        if t < best_t:
            best_t, best_tiles = t, dict(tiles)
    return best_tiles


def get_tiles(spec, backend_tag: str, route: str, args, kw) -> dict[str, Any]:
    """Resolve the tile kwargs for one dispatch.

    ``route`` is the ops-layer route ("interpret" / "compiled"); ``args``/
    ``kw`` are the call's arrays and statics.  Returns the cached winner for
    this (kernel, backend, bucket, mode), sweeping first when eligible;
    defaults (``tile_space[0]``, i.e. the kernels' built-in constants)
    otherwise.
    """
    bucket = bucket_pow2(spec.bucket(args, kw))
    key = (spec.name, backend_tag, bucket, call_mode(kw))
    hit = _TUNE_CACHE.get(key)
    if hit is not None:
        return dict(hit)
    eligible = route == "compiled" or autotune_enabled()
    if not eligible or _has_tracers(args):
        return dict(spec.tile_space[0])

    def call(tiles):
        return spec.pallas(*args, interpret=route == "interpret",
                           **tiles, **kw)

    winner = _sweep(spec, call)
    _TUNE_CACHE[key] = winner
    _TUNE_SWEEPS.labels(kernel=spec.name, backend=backend_tag).inc()
    return dict(winner)


# --------------------------------------------------------- JSON persistence

def save_cache(path: str | Path = DEFAULT_CACHE_PATH) -> Path:
    """Persist the in-process winners; key format
    ``kernel|backend|bucket|mode``."""
    path = Path(path)
    blob = {"|".join(map(str, key)): tiles
            for key, tiles in sorted(_TUNE_CACHE.items())}
    path.write_text(json.dumps(blob, indent=2) + "\n")
    return path


def load_cache(path: str | Path = DEFAULT_CACHE_PATH) -> int:
    """Load persisted winners (merging over in-process entries); returns the
    number of entries loaded.  Missing file is not an error — tuning is an
    optimization, never a requirement."""
    path = Path(path)
    if not path.exists():
        return 0
    blob = json.loads(path.read_text())
    for key, tiles in blob.items():
        kernel, backend, bucket, mode = key.split("|")
        _TUNE_CACHE[(kernel, backend, int(bucket), mode)] = dict(tiles)
    return len(blob)
