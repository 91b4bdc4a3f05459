"""Streaming population scans: fleet-scale characterization in fixed memory.

Every dense entry point in ``core/substrate.py`` materializes its result (and
its intermediates) with a leading DIMM axis, so population size is capped by
host memory.  This module rebuilds the population axis as a chunked scan:

  * ``PopulationStream`` — a lazy population: total size plus a
    ``chunk(lo, hi) -> DimmBatch`` factory.  ``from_batch`` wraps a resident
    batch (views, no copies); ``population.synthetic_fleet`` synthesizes
    million-DIMM fleets chunk by chunk from the counter-hash RNG.
  * ``stream_population`` — THE driver: fixed-size chunks over the DIMM axis
    (``sharding.chunk_spans``, chunk-over-mesh aware), ragged tail clone-
    padded so ONE compiled program serves every chunk and every fleet size,
    per-chunk programs run with buffer donation on the chunk arrays
    (``substrate._chunk_jitted``), results folded through online reductions.
  * Online reductions — ``Sum`` (exact integers via ``packing``, widened f64
    for floats), ``Min``/``Max`` (elementwise, with the attaining serial),
    ``Welford`` (streaming mean/variance), ``Collect`` (explicit opt-in
    materialization for small populations / parity tests).
  * Streamed entry points — ``stream_profile_population``,
    ``stream_lifetime_population``, ``stream_shuffling_gain``,
    ``stream_error_summary`` (device-side grid reduction + bit-packed fail
    maps), ``stream_bit_signature``, and ``stream_discover_generations``
    (incremental generation clustering as chunks flow through).

Exactness contract (see ARCHITECTURE.md "streaming population axis"):
per-DIMM outputs (timing tables, per-DIMM counters) are BIT-IDENTICAL to the
dense path at any chunk size — per-DIMM computation is independent along D
and the counter-hash RNG is keyed by serial, never by batch position, so
chunking cannot change draws.  Cross-DIMM integer reductions (error counts,
stale tallies, min/argmin tables) are exact and chunk-invariant.  Cross-DIMM
float reductions (Welford moments, lambda totals) are f64-widened and
documented as tolerance-stable, not bit-stable, across chunk sizes.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecc as _ecc
from repro.core.geometry import DimmGeometry
from repro.core.latency import (DEFAULT_ITERS, DEFAULT_PATTERNS,
                                PATTERN_STRESS, access_vdd_shift,
                                retention_stress)
from repro.core.packing import narrow_counts, pack_bool
from repro.core.substrate import (DimmBatch, _LEAVES, _axis_context,
                                  _chunk_jitted, _geom_consts, _lifetime_impl,
                                  _mesh_key, _op_grid_impl, _pack_coeffs,
                                  _pad0, _profile_impl,
                                  _resolve_rows, _row_lambda_impl,
                                  _run_sharded, _shuffling_impl,
                                  condition_adders, donation_enabled,
                                  erf_evals, lifetime_adders,
                                  operating_grid_tables, pattern_stress,
                                  sweep_steps)
from repro.core.timing import PARAMS, VDD_STD
from repro.obs import REGISTRY as _OBS_REGISTRY
from repro.sharding import chunk_spans

# chunk outputs rarely share a (shape, dtype) with the donated chunk leaves;
# XLA warns per-compile about the buffers it could not reuse, which is
# expected here — donation is for releasing chunk inputs early, not aliasing
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


# Streaming throughput accounting (obs layer, ARCHITECTURE 3h).  Chunk
# dispatches and folded DIMMs are counted at the HOST chunk boundary — the
# clock the DIMMs/s ROADMAP gate ticks against.  A chunk's execution shows
# in a profiler trace as its program's own device event.
_OBS_CHUNKS = _OBS_REGISTRY.counter(
    "repro_stream_chunks_total",
    "chunk programs dispatched by the streaming driver, by entry point",
    labelnames=("entry",))
_OBS_DIMMS = _OBS_REGISTRY.counter(
    "repro_stream_dimms_total",
    "DIMMs folded through streaming scans (clone-padding excluded)")


# ------------------------------------------------------------- the stream

def slice_batch(batch: DimmBatch, lo: int, hi: int) -> DimmBatch:
    """[lo, hi) population slice of a resident batch — numpy views, no copy."""
    return dataclasses.replace(
        batch, **{n: np.asarray(getattr(batch, n))[lo:hi] for n in _LEAVES})


def pad_batch(batch: DimmBatch, pad: int) -> DimmBatch:
    """Clone-pad the DIMM axis (repeat the last DIMM ``pad`` times).  The
    clone's serial travels with it, so its (discarded) draws are that DIMM's
    and every kept DIMM's draws are untouched — the ``_pad0`` rule."""
    if pad == 0:
        return batch
    return jax.tree.map(lambda a: _pad0(a, pad), batch)


@dataclass
class PopulationStream:
    """A population that is never resident: D plus a chunk factory.

    ``chunk_fn(lo, hi)`` must be a pure function of the global serial range —
    never of chunk position — so any chunk partition yields the same DIMMs
    (the streaming sibling of the global-index RNG rule)."""
    n_dimms: int
    geom: DimmGeometry
    chunk_fn: Callable[[int, int], DimmBatch]

    @classmethod
    def from_batch(cls, batch: DimmBatch) -> "PopulationStream":
        return cls(batch.n_dimms, batch.geom,
                   lambda lo, hi: slice_batch(batch, lo, hi))

    def chunk(self, lo: int, hi: int) -> DimmBatch:
        if not 0 <= lo < hi <= self.n_dimms:
            raise ValueError(f"chunk [{lo}, {hi}) outside population "
                             f"[0, {self.n_dimms})")
        return self.chunk_fn(lo, hi)

    def materialize(self) -> DimmBatch:
        """The full dense batch (small populations / parity tests only)."""
        return self.chunk(0, self.n_dimms)


def as_stream(source) -> PopulationStream:
    if isinstance(source, PopulationStream):
        return source
    if isinstance(source, DimmBatch):
        return PopulationStream.from_batch(source)
    raise TypeError(f"expected DimmBatch or PopulationStream, "
                    f"got {type(source).__name__}")


# ------------------------------------------------------- online reductions

class Reduction:
    """Folds per-chunk values; ``per_dimm`` declares a leading DIMM axis
    (the driver strips clone-padding and passes chunk serials)."""
    per_dimm = True

    def update(self, value: np.ndarray, serials: np.ndarray) -> None:
        raise NotImplementedError

    def result(self):
        raise NotImplementedError


class Sum(Reduction):
    """Sum over the DIMM axis: exact int64 for integer/bool chunks (adds
    commute — bit-invariant to chunk size and order), f64-widened for float
    chunks (tolerance-stable only)."""

    def __init__(self):
        self._acc: np.ndarray | None = None
        self._mode: str | None = None

    def update(self, value, serials) -> None:
        value = np.asarray(value)
        is_int = np.issubdtype(value.dtype, np.integer) \
            or value.dtype == np.bool_
        mode = "int" if is_int else "float"
        if self._mode is None:
            self._mode = mode
        elif self._mode != mode:
            raise TypeError("Sum fed mixed integer/float chunks")
        part = value.astype(np.int64 if is_int else np.float64).sum(axis=0)
        self._acc = part if self._acc is None else self._acc + part

    def result(self):
        return self._acc


class _Extreme(Reduction):
    """Elementwise min/max over the DIMM axis, tracking the serial that
    attains it (first-in-serial-order on ties — chunk-invariant because the
    scan walks serials in order)."""

    def __init__(self, op):
        self._op = op  # np.minimum or np.maximum
        self._pick = np.argmin if op is np.minimum else np.argmax
        self._val: np.ndarray | None = None
        self._serial: np.ndarray | None = None

    def update(self, value, serials) -> None:
        value = np.asarray(value)
        idx = self._pick(value, axis=0)
        cv = np.take_along_axis(value, idx[None], axis=0)[0]
        cs = np.asarray(serials)[idx]
        if self._val is None:
            self._val, self._serial = cv, cs
            return
        # strict comparison: on a tie the earlier (already-held) serial wins
        better = cv < self._val if self._op is np.minimum else cv > self._val
        self._val = np.where(better, cv, self._val)
        self._serial = np.where(better, cs, self._serial)

    def result(self):
        return {"value": self._val, "serial": self._serial}


class Min(_Extreme):
    def __init__(self):
        super().__init__(np.minimum)


class Max(_Extreme):
    def __init__(self):
        super().__init__(np.maximum)


class Welford(Reduction):
    """Streaming mean/variance over the DIMM axis (Chan parallel merge in
    f64).  Tolerance-stable — NOT bit-stable — across chunk sizes."""

    def __init__(self):
        self.n = 0
        self._mean: np.ndarray | None = None
        self._m2: np.ndarray | None = None

    def update(self, value, serials) -> None:
        value = np.asarray(value, np.float64)
        n_b = value.shape[0]
        mean_b = value.mean(axis=0)
        m2_b = ((value - mean_b) ** 2).sum(axis=0)
        if self._mean is None:
            self.n, self._mean, self._m2 = n_b, mean_b, m2_b
            return
        n = self.n + n_b
        delta = mean_b - self._mean
        self._mean = self._mean + delta * (n_b / n)
        self._m2 = self._m2 + m2_b + delta ** 2 * (self.n * n_b / n)
        self.n = n

    def result(self):
        var = self._m2 / self.n if self.n else self._m2
        return {"mean": self._mean, "var": var, "count": self.n}


class Collect(Reduction):
    """Materialize per-DIMM chunk outputs (the dense result).  Explicit
    opt-in: fine for parity tests and small fleets, defeats the point at
    scale — the streamed summaries are the fleet-scale product."""

    def __init__(self):
        self._parts: list[np.ndarray] = []

    def update(self, value, serials) -> None:
        self._parts.append(np.asarray(value))

    def result(self):
        return np.concatenate(self._parts, axis=0)


class Passthrough(Reduction):
    """For chunk outputs the device already reduced over the chunk's DIMMs
    (no leading DIMM axis): fold with elementwise addition (or a supplied
    merge).  Exactness follows the dtype the program ships: integer chunk
    aggregates fold exactly, float ones are only tolerance-stable."""
    per_dimm = False

    def __init__(self, merge=None):
        self._merge = merge if merge is not None else (lambda a, b: a + b)
        self._acc = None

    def update(self, value, serials) -> None:
        value = np.asarray(value)
        self._acc = value if self._acc is None \
            else self._merge(self._acc, value)

    def result(self):
        return self._acc


# --------------------------------------------------------------- the driver

def _padded_width(chunk_size: int, mesh) -> int:
    """The one compiled chunk shape: ``chunk_size`` rounded up to the mesh
    (mirrors ``chunk_spans``).  Every chunk — including a whole fleet smaller
    than a chunk — is clone-padded to THIS width, so the chunk program
    compiles once per (geometry, statics) and is reused across every fleet
    size.  Padding to the span width instead would recompile per small-fleet
    size, silently costing the dense path's per-D re-lowering all over again.
    """
    if mesh is not None:
        chunk_size += (-chunk_size) % int(mesh.devices.size)
    return chunk_size


def stream_population(source, program, reducers: dict, *,
                      chunk_size: int = 1024, mesh=None) -> dict:
    """Run ``program`` over fixed-size population chunks, folding outputs
    through online reductions — no full-population tensor is ever resident.

    ``program(chunk_batch, keep, lo) -> dict[str, array]`` is called once per
    chunk with the clone-PADDED chunk (every chunk the same shape, so the
    jitted chunk program compiles exactly once per fleet, any size) and a
    ``keep`` (chunk_size,) bool mask that is False on padding — programs that
    reduce over the chunk's DIMM axis *on device* must mask with it.
    ``reducers`` maps output names to ``Reduction`` instances; per-DIMM
    outputs (leading padded-chunk axis) are pad-stripped by the driver before
    folding.  ``mesh`` shards each chunk over the DIMM axis
    (``sharding.chunk_spans`` rounds the chunk size up to the mesh, the
    chunk-over-mesh composition), which composes with — and cannot change —
    the per-DIMM results, so the folded summaries are sharding-invariant too.

    Returns ``{name: reduction.result()}`` plus ``n_dimms`` / ``n_chunks`` /
    ``chunk_size``.
    """
    stream = as_stream(source)
    spans = chunk_spans(stream.n_dimms, chunk_size, mesh)
    full = _padded_width(chunk_size, mesh)
    for lo, hi in spans:
        batch = stream.chunk(lo, hi)
        keep = np.arange(full) < (hi - lo)
        out = program(pad_batch(batch, full - (hi - lo)), keep, lo)
        _OBS_DIMMS.inc(hi - lo)
        serials = np.asarray(batch.serial)
        for name, red in reducers.items():
            value = np.asarray(out[name])
            if red.per_dimm:
                value = value[:hi - lo]
            red.update(value, serials)
    res = {name: red.result() for name, red in reducers.items()}
    res.update(n_dimms=stream.n_dimms, n_chunks=len(spans), chunk_size=full)
    return res


def _chunk_call(name: str, impl, args, statics: dict, donate: tuple,
                batch_argnums: tuple, mesh):
    """One chunk dispatch: the donated cached jit, or the sharded route when
    a mesh is given (shard_map has its own program cache; donation does not
    compose with it and is skipped).  Also the streaming layer's one
    instrumentation point: the chunk counter."""
    _OBS_CHUNKS.labels(entry=name).inc()
    if mesh is None:
        return _chunk_jitted(name, impl, statics, donate)(*args)
    return _run_sharded(name, mesh, impl, args, statics, batch_argnums)


# ------------------------------------------------- streamed profiling sweep

def stream_profile_population(source, *, chunk_size: int = 1024,
                              region: str = "worst", temp_C: float = 55.0,
                              refresh_ms: float = 64.0,
                              vdd: float = VDD_STD, guard_cycles: int = 1,
                              multibit_only: bool = False,
                              patterns=DEFAULT_PATTERNS,
                              iters: int = DEFAULT_ITERS, banks: int = 1,
                              axes=PARAMS, retention: bool = False,
                              collect: bool = False, mesh=None) -> dict:
    """DIVA / conventional profiling of an arbitrarily large population in
    fixed memory: the streamed ``profile_population_arrays``.

    Per-DIMM tables are bit-identical to the dense path at any chunk size
    (chunking never keys the RNG); the fleet summary is folded online —
    ``tables_min`` / ``tables_max`` (elementwise over the population, with
    the attaining serial: the fleet's fastest/slowest corner per parameter)
    and ``tables_stats`` (Welford mean/var).  ``collect=True`` additionally
    concatenates the per-DIMM (D, [banks,] len(axes)) tables (small fleets /
    parity tests).  ``mesh`` shards each chunk over the DIMM axis.

    ``axes`` / ``vdd`` / ``retention`` extend the sweep beyond the 4-timing
    prefix exactly as in ``profile_population_arrays``; the per-axis context
    tables are rebuilt host-side per chunk (they are pure per-DIMM functions
    of the chunk's leaves, so the cross-product grid is never resident at
    fleet scale) and fold through the same online reductions.  At the
    defaults the chunk program is the pre-operating-point one, bit for bit.
    """
    stream = as_stream(source)
    if stream.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={stream.geom.subarrays}")
    axes = tuple(axes)
    rows = _resolve_rows(region, stream.geom)
    if rows.ndim != 1:
        raise ValueError("stream_profile_population takes a shared (Rr,) "
                         "region; use the dense path for per-DIMM regions")
    rows_j = jnp.asarray(rows, jnp.int32)
    stress = jnp.asarray(pattern_stress(patterns))
    statics = dict(guard_cycles=guard_cycles, iters=iters,
                   multibit=multibit_only, banks=banks, axes=axes,
                   retention=retention)

    red: dict[str, Reduction] = {}
    if collect:
        red["tables"] = Collect()
    red.update(tables_min=Min(), tables_max=Max(), tables_stats=Welford())

    def program(batch, keep, lo):
        adder = jnp.asarray(condition_adders(batch, temp_C, refresh_ms))
        args = (batch, rows_j, stress, adder)
        donate, argnums = (0, 3), (0, 3)
        ctx_d, ctx_g = _axis_context(batch, axes, temp_C=temp_C,
                                     refresh_ms=refresh_ms, vdd=vdd)
        if ctx_d is not None:
            args = args + (ctx_d, ctx_g)
            donate, argnums = (0, 3, 4), (0, 3, 4)
        tables, steps = _chunk_call("stream_profile", _profile_impl, args,
                                    statics, donate=donate,
                                    batch_argnums=argnums, mesh=mesh)
        tables = np.asarray(tables if banks > 1 else tables[:, 0])
        sweep_steps("stream_profile", steps)
        return {name: tables for name in red}

    return stream_population(stream, program, red,
                             chunk_size=chunk_size, mesh=mesh)


# ------------------------------------------------- streamed lifetime scan

def stream_lifetime_population(source, ages, temps, *,
                               chunk_size: int = 1024,
                               refresh_ms: float = 64.0,
                               region: str = "worst", guard_cycles: int = 1,
                               multibit: bool = True,
                               patterns=DEFAULT_PATTERNS,
                               iters: int = DEFAULT_ITERS,
                               diagnostics: bool = True, banks: int = 1,
                               collect: bool = False, mesh=None) -> dict:
    """The streamed ``lifetime_population``: the whole online re-profiling
    lifecycle over an arbitrarily large fleet in fixed memory.

    ``ages`` / ``temps`` are per-epoch (E,) schedules shared by the fleet
    (per-DIMM (E, D) schedules are a dense-path feature).  Online summaries:
    per-epoch timing Welford stats + min/max-with-serial, exact per-epoch
    ``stale_count`` (how many DIMMs' previous table went unsafe — the fleet
    re-profiling urgency signal) and f64-widened ``ecc_lambda_total``.
    ``collect=True`` additionally materializes per-DIMM trajectories
    (``timings`` (D, E, [banks,] 4) etc. — note the DIMM-leading layout;
    the dense path's epoch-leading arrays are one ``moveaxis`` away).
    """
    stream = as_stream(source)
    if stream.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={stream.geom.subarrays}")
    ages = np.asarray(ages, np.float32)
    temps = np.asarray(temps, np.float64)
    if ages.ndim != 1 or temps.ndim != 1:
        raise ValueError("stream_lifetime_population takes shared (E,) "
                         "schedules; per-DIMM (E, D) schedules are dense-only")
    rows_j = jnp.asarray(_resolve_rows(region, stream.geom), jnp.int32)
    stress = jnp.asarray(pattern_stress(patterns))
    statics = dict(guard_cycles=guard_cycles, iters=iters, multibit=multibit,
                   diagnostics=diagnostics, banks=banks)
    sq = (lambda a: a[:, :, 0]) if banks == 1 else (lambda a: a)

    red: dict[str, Reduction] = {"timings_stats": Welford(),
                                 "timings_min": Min(), "timings_max": Max()}
    names = {"timings_stats": "timings", "timings_min": "timings",
             "timings_max": "timings"}
    if diagnostics:
        red.update(stale_count=Sum(), ecc_lambda_total=Sum())
        names.update(stale_count="stale", ecc_lambda_total="ecc")
    if collect:
        red["timings"] = Collect()
        names["timings"] = "timings"
        if diagnostics:
            red.update(stale_fail=Collect(), ecc_lambda=Collect())
            names.update(stale_fail="stale", ecc_lambda="ecc")

    def program(batch, keep, lo):
        adders = lifetime_adders(batch, ages, temps, refresh_ms)   # (E, C)
        out = _chunk_call("stream_lifetime", _lifetime_impl,
                          (batch, rows_j, stress, jnp.asarray(adders.T)),
                          statics, donate=(0, 3), batch_argnums=(0, 3),
                          mesh=mesh)
        vals = {"timings": sq(np.asarray(out[0]))}     # (C, E, [banks,] 4)
        if diagnostics:
            vals["stale"] = sq(np.asarray(out[1]))     # (C, E[, banks])
            vals["ecc"] = sq(np.asarray(out[2]))
        return {name: vals[names[name]] for name in red}

    out = stream_population(stream, program, red,
                            chunk_size=chunk_size, mesh=mesh)
    out["ages"], out["temps"] = ages, temps
    return out


# ------------------------------------------------- streamed Fig 17 scoring

def stream_shuffling_gain(probs_source, n_dimms: int | None = None, *,
                          chunk_size: int = 2048, seed: int = 0,
                          n_accesses: int = 2000, collect: bool = False,
                          mesh=None) -> dict:
    """The streamed ``shuffling_gain_population``: Fig 17 ECC scoring over an
    arbitrarily large fleet of (9, 64) burst-bit error profiles.

    ``probs_source`` is a (D, 9, 64) array or a ``(lo, hi) -> (C, 9, 64)``
    chunk factory (with ``n_dimms`` given).  Per-DIMM seeds are ``seed +
    global index`` — chunk-invariant by construction.  All seven codeword
    counters fold as EXACT int64 sums, so the fleet correctable fractions
    are bit-invariant to chunking; ``collect=True`` keeps the per-DIMM
    counters too.
    """
    if callable(probs_source):
        if n_dimms is None:
            raise ValueError("n_dimms is required with a chunk factory")
        probs_fn, D = probs_source, int(n_dimms)
    else:
        probs = np.asarray(probs_source, np.float32)
        if probs.ndim == 2:
            probs = probs[None]
        probs_fn, D = (lambda lo, hi: probs[lo:hi]), probs.shape[0]

    from repro.kernels import ops
    statics = dict(n_accesses=n_accesses, pallas=ops.use_pallas())
    keys = ("total", "corrected_no_shuffle", "corrected_shuffle",
            "uncorrectable_no_shuffle", "uncorrectable_shuffle",
            "undetected_no_shuffle", "undetected_shuffle")

    spans = chunk_spans(D, chunk_size, mesh)
    full = _padded_width(chunk_size, mesh)
    red: dict[str, Reduction] = {f"{k}_sum": Sum() for k in keys}
    if collect:
        red.update({k: Collect() for k in keys})
    for lo, hi in spans:
        chunk = np.asarray(probs_fn(lo, hi), np.float32)
        if chunk.shape != (hi - lo, 9, 64):
            raise ValueError(f"chunk factory returned {chunk.shape}, "
                             f"expected {(hi - lo, 9, 64)}")
        seeds = (seed + np.arange(lo, hi)).astype(np.uint32)
        pad = full - (hi - lo)
        out = _chunk_call(
            "stream_shuffling", _shuffling_impl,
            (jnp.asarray(_pad0(chunk, pad)), jnp.asarray(_pad0(seeds, pad))),
            statics, donate=(0, 1), batch_argnums=(0, 1), mesh=mesh)
        _OBS_DIMMS.inc(hi - lo)
        for k, arr in zip(keys, out):
            v = np.asarray(arr, np.int64)[:hi - lo]
            red[f"{k}_sum"].update(v, seeds)
            if collect:
                red[k].update(v, seeds)
    res = {name: r.result() for name, r in red.items()}
    total = max(int(res["total_sum"]), 1)
    res["frac_no_shuffle"] = int(res["corrected_no_shuffle_sum"]) / total
    res["frac_shuffle"] = int(res["corrected_shuffle_sum"]) / total
    res["gain"] = (int(res["corrected_shuffle_sum"])
                   - int(res["corrected_no_shuffle_sum"])) / total
    res.update(n_dimms=D, n_chunks=len(spans), chunk_size=full)
    return res


# --------------------------------------- streamed fail-grid fleet summary

def _error_summary_impl(row_src, d_mat, coeffs, keep, *,
                        cols: int, pallas: bool, threshold: float,
                        voltage: bool = False, retention: bool = False):
    """One chunk of the fleet fail-grid summary, reduced ON DEVICE: the
    (C, mats, rows, cols) grid tensor exists only chunk-sized and only on
    device; what crosses to host is per-DIMM scalars, the fleet cell-sum,
    exact per-cell hot counts, and a bit-packable per-DIMM row fail map.
    ``keep`` masks clone-padding out of the cross-DIMM aggregates.  Static
    ``voltage``/``retention`` add the kernel's supply shift and retention
    channel (15-coefficient rows); both off is the plain 9-wide graph."""
    from repro.kernels import ops
    grids = ops.fail_prob_batch(row_src, d_mat, coeffs, cols=cols,
                                voltage=voltage, retention=retention,
                                pallas=pallas)              # (C, M, R, cols)
    keep4 = keep[:, None, None, None]
    return {
        "lam_total": grids.sum(axis=(1, 2, 3)),             # (C,) per-DIMM
        "worst_cell": grids.max(axis=(1, 2, 3)),            # (C,) per-DIMM
        "grid_sum": jnp.where(keep4, grids, 0.0).sum(axis=0),
        "hot_cells": ((grids > threshold) & keep4).sum(axis=0)
        .astype(jnp.int32),                                 # (M, R, cols)
        "row_fail": jnp.any(grids > threshold, axis=(1, 3)),  # (C, R) bool
    }


_ERR_SHARD_CACHE: dict = {}


def _error_summary_sharded(mesh, args, statics: dict):
    """Sharded route for the error-summary chunk program.  Unlike
    ``_run_sharded`` (every output P(dimm-axis)), the fleet aggregates here
    are reduced ACROSS the chunk on device, so they leave shard_map
    replicated (psum over the mesh axis) while per-DIMM outputs stay
    sharded — a mixed out-spec ``_run_sharded`` cannot express."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import shard_map
    axis = mesh.axis_names[0]
    key = (_mesh_key(mesh), tuple(sorted(statics.items())))
    prog = _ERR_SHARD_CACHE.get(key)
    if prog is None:
        def fn(row_src, d_mat, coeffs, keep):
            out = _error_summary_impl(row_src, d_mat, coeffs, keep, **statics)
            out["grid_sum"] = jax.lax.psum(out["grid_sum"], axis)
            out["hot_cells"] = jax.lax.psum(out["hot_cells"], axis)
            return out
        specs = {"lam_total": P(axis), "worst_cell": P(axis),
                 "grid_sum": P(), "hot_cells": P(), "row_fail": P(axis)}
        prog = _ERR_SHARD_CACHE[key] = jax.jit(shard_map(
            fn, mesh, in_specs=(P(axis), P(), P(axis), P(axis)),
            out_specs=specs))
    return prog(*args)


def stream_error_summary(source, param: str, t_op: float, *,
                         chunk_size: int = 2048, temp_C: float = 85.0,
                         refresh_ms: float = 64.0, vdd: float = VDD_STD,
                         retention: bool = False, pattern: str = "0101",
                         chip: int = 0, subarray: int = 0,
                         threshold: float = 0.5,
                         collect_fail_maps: bool = False, mesh=None) -> dict:
    """Fleet-scale failure-probability summary WITHOUT materializing the
    (D, mats, rows, cols) grids the dense ``fail_prob_grids`` returns.

    Per chunk, the grids are computed AND reduced on device (the chunk
    program's outputs are per-DIMM scalars plus cell-resolution fleet
    aggregates); online reductions fold chunks into:

      * ``lam_stats`` / ``lam_min`` / ``lam_max`` — per-DIMM expected-failure
        mass (Welford + extremes with the attaining serial: the fleet's
        best/worst DIMM);
      * ``grid_sum`` — (mats, rows, cols) fleet cell-sum (f64-widened): the
        population heatmap, Fig 7 at fleet scale;
      * ``hot_cells`` — (mats, rows, cols) EXACT count of DIMMs whose cell
        fails with p > ``threshold`` (chunk-invariant integer fold);
      * ``fail_maps`` (opt-in) — per-DIMM (R,) row fail maps, bit-packed
        8 cells/byte (``packing.pack_bool``) before they go resident.

    ``vdd`` / ``retention`` set the kernel's channel flags
    (``ops.fail_prob_batch(voltage=, retention=)``): a non-nominal
    supply shifts the access channel, and ``retention=True`` adds the
    refresh/temperature retention channel riding the swept parameter's
    design-variation sum (canonically ``param="tras"``, the charge-restore
    knob).  At the defaults the chunk program is the plain ``fail_prob``
    one, verbatim.
    """
    from repro.kernels import ops
    stream = as_stream(source)
    pidx = PARAMS.index(param)
    voltage = vdd != VDD_STD
    stress = np.float32(PATTERN_STRESS[pattern])
    _, d_mat, _ = _geom_consts(stream.geom)
    d_mat = jnp.asarray(d_mat)
    statics = dict(cols=stream.geom.cols_per_mat, pallas=ops.use_pallas(),
                   threshold=threshold, voltage=voltage, retention=retention)
    ret_x = retention_stress(temp_C, refresh_ms, vdd)
    packed_maps: list = []

    red = {"lam_stats": Welford(), "lam_min": Min(), "lam_max": Max(),
           "worst_cell_max": Max(), "grid_sum": Passthrough(),
           "hot_cells": Passthrough()}
    names = {"lam_stats": "lam_total", "lam_min": "lam_total",
             "lam_max": "lam_total", "worst_cell_max": "worst_cell",
             "grid_sum": "grid_sum", "hot_cells": "hot_cells"}

    def program(batch, keep, lo):
        adder = jnp.asarray(condition_adders(batch, temp_C, refresh_ms))
        shift = (access_vdd_shift(np.asarray(batch.vdd_coef, np.float32), vdd)
                 if voltage or retention else None)
        coeffs = _pack_coeffs(batch, pidx, np.float32(t_op), stress, adder,
                              chip, subarray, shift, ret_x)
        args = (jnp.asarray(batch.row_src[:, subarray]), d_mat, coeffs,
                jnp.asarray(keep))
        # hand-rolled dispatch (mixed out-specs) — count the chunk here
        _OBS_CHUNKS.labels(entry="stream_error_summary").inc()
        if mesh is None:
            out = _chunk_jitted("stream_error_summary", _error_summary_impl,
                                statics, donate=(0, 2))(*args)
        else:
            out = _error_summary_sharded(mesh, args, statics)
        out = {k: np.asarray(v) for k, v in out.items()}
        # fleet aggregates fold across many chunks: widen before the host add
        out["grid_sum"] = out["grid_sum"].astype(np.float64)
        out["hot_cells"] = out["hot_cells"].astype(np.int64)
        if collect_fail_maps:
            packed_maps.append(pack_bool(out["row_fail"][:int(keep.sum())]))
        return {name: out[names[name]] for name in red}

    out = stream_population(stream, program, red,
                            chunk_size=chunk_size, mesh=mesh)
    if collect_fail_maps:
        out["fail_maps"] = packed_maps
    return out


# --------------------------------------- streamed N-axis operating grid

def stream_operating_grid(source, points, *, chunk_size: int = 1024,
                          region: str = "worst", patterns=DEFAULT_PATTERNS,
                          iters: int = DEFAULT_ITERS,
                          multibit_only: bool = False, banks: int = 1,
                          retention: bool = True, collect: bool = False,
                          mesh=None) -> dict:
    """The streamed ``operating_grid_arrays``: every DIMM of an arbitrarily
    large fleet evaluated at every ``OperatingPoint`` in ``points``, with
    the (D, G) result grid NEVER fully resident — per-point outcomes fold
    through online reductions as chunks flow through.

    Per chunk, the host tables (per-DIMM condition adders and voltage
    shifts) are rebuilt from the chunk's leaves — pure per-DIMM functions,
    so chunking cannot change them — and the jitted grid scan runs once.
    Folded summaries, all (G[, banks])-shaped over the grid:

      * ``fail_count`` — EXACT int64 count of DIMMs whose region trips at
        each point (chunk-invariant integer fold);
      * ``fail_stats`` — Welford over the 0/1 outcomes: the population
        failure probability per point (the Pareto frontier's z-axis);
      * ``lam_stats`` / ``lam_max`` — expected-failure-mass moments and the
        fleet's worst DIMM per point (with the attaining serial).

    ``collect=True`` additionally keeps the per-DIMM (D, G[, banks])
    ``fails`` / ``lam`` arrays (small fleets / parity tests).  Per-DIMM
    DECISIONS are bit-identical to the dense path at any chunk size — the
    draw key is ``timing.op_point_key`` of the point's quantized
    coordinates plus the DIMM serial, never a batch position; per-DIMM
    lambdas are float32 reductions whose fusion varies with the chunk
    program's width, i.e. tolerance-stable per the module contract.
    """
    stream = as_stream(source)
    if stream.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={stream.geom.subarrays}")
    points = list(points)
    rows = _resolve_rows(region, stream.geom)
    if rows.ndim != 1:
        raise ValueError("stream_operating_grid takes a shared (Rr,) "
                         "region; use the dense path for per-DIMM regions")
    rows_j = jnp.asarray(rows, jnp.int32)
    stress = jnp.asarray(pattern_stress(patterns))
    statics = dict(iters=iters, multibit=multibit_only, banks=banks,
                   retention=retention)
    sq = (lambda a: a[..., 0]) if banks == 1 else (lambda a: a)

    red: dict[str, Reduction] = {"fail_count": Sum(), "fail_stats": Welford(),
                                 "lam_stats": Welford(), "lam_max": Max()}
    names = {"fail_count": "fails", "fail_stats": "fails",
             "lam_stats": "lam", "lam_max": "lam"}
    if collect:
        red.update(fails=Collect(), lam=Collect())
        names.update(fails="fails", lam="lam")

    def program(batch, keep, lo):
        t_g, adders_dg, shifts_dg, keys_g, retx_g = \
            operating_grid_tables(batch, points)
        fails, lam = _chunk_call(
            "stream_op_grid", _op_grid_impl,
            (batch, rows_j, stress, jnp.asarray(t_g),
             jnp.asarray(adders_dg), jnp.asarray(shifts_dg),
             jnp.asarray(keys_g), jnp.asarray(retx_g)),
            statics, donate=(0, 4, 5), batch_argnums=(0, 4, 5), mesh=mesh)
        vals = {"fails": sq(np.asarray(fails)), "lam": sq(np.asarray(lam))}
        return {name: vals[names[name]] for name in red}

    out = stream_population(stream, program, red,
                            chunk_size=chunk_size, mesh=mesh)
    out["points"] = points
    return out


# ------------------------------------- streamed signatures + generations

def stream_bit_signature(counts_fn, n_dimms: int, *, chunk_size: int = 4096,
                         mesh=None) -> np.ndarray:
    """Streamed ``bit_signature_population``: (D, S, nbits) signatures from a
    ``(lo, hi) -> (C, S, R)`` integer-count chunk factory.  Signatures are a
    pure per-DIMM map (exact integer kernel + one power-of-two divide), so
    the concatenated result is bit-identical to the dense call at any chunk
    size."""
    from repro.discovery.signatures import bit_signature_population
    parts = [bit_signature_population(np.asarray(counts_fn(lo, hi)),
                                      mesh=mesh)
             for lo, hi in chunk_spans(n_dimms, chunk_size, mesh)]
    return np.concatenate(parts, axis=0) if parts \
        else np.zeros((0, 0, 0), np.float32)


# ------------------------------------------------- streamed SECDED scrub

def _scrub_impl(code, *, pallas: bool):
    """One scrub chunk: syndrome (kernel dispatch) -> single-bit correction.
    Returns (fixed (C, 72) i32, status (C,) i32).  ``fixed`` has exactly the
    input's shape and dtype ON PURPOSE: the chunk program donates ``code``,
    and XLA aliases the corrected output onto the donated buffer — this is
    the one streamed entry point where donation reclaims a whole chunk of
    peak RSS (outputs elsewhere are reductions, which can't alias)."""
    # deferred import: kernels.ops pulls in every kernel module, which import
    # core.latency -> core.__init__ -> this module (cycle at import time)
    from repro.kernels import ops as _kops
    code = jnp.asarray(code, jnp.int32)
    syn = _kops.secded_syndrome(code, pallas=pallas)
    return _ecc.correct_codewords(code, syn)


def stream_secded_scrub(source, n_words: int | None = None, *,
                        chunk_size: int = 262_144, collect: bool = False,
                        donate: bool = True, pallas: bool | None = None
                        ) -> dict:
    """Streamed controller-side ECC scrub: run SECDED(72,64) syndrome +
    single-bit correction over a stream of codewords in fixed memory — the
    paper's DIVA-Shuffling ECC path at checkpoint-scrubbing scale.

    ``source`` is a (N, 72) 0/1 array, or a ``(lo, hi) -> (hi-lo, 72)``
    chunk factory (then ``n_words`` is required and no full array is ever
    resident).  Each chunk's codeword buffer is donated to the chunk program
    (``donate=False`` or ``REPRO_NO_DONATE=1`` opts out for A/B memory
    measurement); the corrected chunk aliases it, so the scan's peak RSS is
    one chunk buffer smaller than an undonated scan — asserted by the slow
    RSS regression test.  Zero-padded tail rows scrub as clean and are
    sliced off before counting, so counts and collected words are exact at
    any chunk size.

    Returns clean/corrected/uncorrectable counts (+ ``codewords`` (N, 72)
    when ``collect``).
    """
    if callable(source):
        if n_words is None:
            raise ValueError("n_words is required with a chunk factory")
        fetch = source
    else:
        arr = np.asarray(source)
        n_words = arr.shape[0]
        fetch = lambda lo, hi: arr[lo:hi]
    if pallas is None:
        from repro.kernels import ops as _kops
        pallas = _kops.use_pallas()
    statics = dict(pallas=pallas)
    donate_argnums = (0,) if donate else ()
    spans = chunk_spans(n_words, chunk_size, None)
    counts = np.zeros(3, np.int64)
    collected: list[np.ndarray] = []
    for lo, hi in spans:
        chunk = np.asarray(fetch(lo, hi), np.int32)
        m = hi - lo
        if chunk.shape != (m, _ecc.CODE_BITS):
            raise ValueError(f"scrub chunk [{lo}:{hi}) has shape "
                             f"{chunk.shape}, want ({m}, {_ecc.CODE_BITS})")
        if m < chunk_size:
            chunk = np.pad(chunk, ((0, chunk_size - m), (0, 0)))
        fixed, status = _chunk_call("secded_scrub", _scrub_impl,
                                    (jnp.asarray(chunk),), statics,
                                    donate_argnums, (0,), None)
        counts += np.bincount(np.asarray(status)[:m], minlength=3)[:3]
        if collect:
            collected.append(np.asarray(fixed[:m]))
        del fixed  # drop the (possibly input-aliased) chunk before the next
    res = {"n_words": int(n_words), "n_chunks": len(spans),
           "chunk_size": int(chunk_size),
           "clean": int(counts[0]), "corrected": int(counts[1]),
           "uncorrectable": int(counts[2]),
           "donated": bool(donate and donation_enabled())}
    if collect:
        res["codewords"] = (np.concatenate(collected) if collected
                            else np.zeros((0, _ecc.CODE_BITS), np.int32))
    return res


def _campaign_impl(batch: DimmBatch, t_op, stress, adder, *, pidx: int,
                   iters: int, seed: int, internal: bool, pallas: bool):
    lam, evals = _row_lambda_impl(batch, t_op, stress, adder, pidx=pidx,
                                  iters=iters, internal=internal,
                                  pallas=pallas)
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda s: jax.random.fold_in(base, s.astype(jnp.int32)))(
        batch.serial)
    return jax.vmap(lambda k, l: jax.random.poisson(k, l))(keys, lam), evals


def hash_poisson_counts(batch: DimmBatch, param: str, t_op: float, *,
                        temp_C: float = 85.0, refresh_ms: float = 64.0,
                        patterns=DEFAULT_PATTERNS, iters: int = DEFAULT_ITERS,
                        seed: int = 0, mesh=None) -> np.ndarray:
    """Synthetic observed campaign counts for a (chunk) batch: the device
    row-lambda sweep followed by per-DIMM Poisson draws whose PRNG key is
    folded from the DIMM's SERIAL — never its batch position — so a chunked
    campaign draws the same counts at any chunk size (the streaming sibling
    of ``DimmModel.sample_row_counts``, which is per-DIMM-object and
    host-bound).  Returns (C, S, R) int64 external-order counts."""
    from repro.kernels import ops
    g = batch.geom
    stress = jnp.asarray(pattern_stress(patterns))
    adder = jnp.asarray(condition_adders(batch, temp_C, refresh_ms))
    statics = dict(pidx=PARAMS.index(param), iters=iters, seed=seed,
                   internal=False, pallas=ops.use_pallas())
    counts, evals = _chunk_call("stream_campaign", _campaign_impl,
                                (batch, np.float32(t_op), stress, adder),
                                statics, donate=(0, 3), batch_argnums=(0, 3),
                                mesh=mesh)
    erf_evals("stream_campaign", evals)
    return np.asarray(counts, np.int64).reshape(
        batch.n_dimms, g.subarrays, g.rows_per_mat)


def stream_discover_generations(source, *, counts_fn=None, param: str = "trp",
                                t_op: float = 7.5, temp_C: float = 85.0,
                                refresh_ms: float = 256.0,
                                chunk_size: int = 4096,
                                threshold: float = 0.85, k_rows: int = 2,
                                campaign_seed: int = 0,
                                collect_labels: bool = True,
                                mesh=None) -> dict:
    """Generation inference as chunks flow through: the streamed sibling of
    the blind-discovery clustering stage, built on
    ``generation.StreamingGenerations`` (incremental leader clustering +
    exact integer canonical-profile accumulation).

    Per chunk: observed counts (``counts_fn(chunk_batch)`` over the clone-
    padded chunk, default the serial-keyed ``hash_poisson_counts`` campaign)
    are dtype-narrowed (``packing.narrow_counts``) before they sit resident,
    signatures run through the bit-signature kernel, features update the
    running clusterer, and the chunk's counts fold into its generation's
    exact canonical sums.  At finalize: per-DIMM labels (bit-identical to
    the dense greedy clusterer — the scan walks serials in order), mean
    canonical profiles (EXACT: integer sums / profile count), and the
    discovered vulnerable rows per generation.
    """
    from repro.discovery.generation import StreamingGenerations
    from repro.discovery.signatures import (bit_signature_population,
                                            signature_features)
    stream = as_stream(source)
    if counts_fn is None:
        counts_fn = functools.partial(
            hash_poisson_counts, param=param, t_op=t_op, temp_C=temp_C,
            refresh_ms=refresh_ms, seed=campaign_seed, mesh=mesh)

    gens = StreamingGenerations(threshold=threshold)
    labels_parts: list[np.ndarray] = []
    serial_parts: list[np.ndarray] = []
    spans = chunk_spans(stream.n_dimms, chunk_size, mesh)
    full = _padded_width(chunk_size, mesh)
    for lo, hi in spans:
        batch = stream.chunk(lo, hi)
        padded = pad_batch(batch, full - (hi - lo))
        counts = narrow_counts(np.asarray(counts_fn(padded))[:hi - lo])
        sigs = bit_signature_population(counts.astype(np.int32), mesh=mesh)
        feats = signature_features(sigs)
        labels = gens.update(feats, counts)
        _OBS_DIMMS.inc(hi - lo)
        if collect_labels:
            labels_parts.append(labels)
            serial_parts.append(np.asarray(batch.serial))
    out = gens.finalize(k_rows=k_rows)
    if collect_labels:
        out["labels"] = gens.resolve_labels(
            np.concatenate(labels_parts) if labels_parts
            else np.zeros(0, np.int64))
        out["serials"] = np.concatenate(serial_parts) if serial_parts \
            else np.zeros(0, np.uint32)
    out.update(n_dimms=stream.n_dimms, n_chunks=len(spans), chunk_size=full)
    return out
