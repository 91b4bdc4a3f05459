"""Batched characterization substrate: the whole DIMM population as one pytree.

``DimmBatch`` lowers a list of ``DimmModel`` objects (core/errors.py) into
stacked arrays — per-DIMM vendor coefficients, chip/subarray offsets,
repair-resolved row-source tables, scramble tables — built once from
``core/population.py`` output.  Everything downstream is array programs:

  * ``fail_prob_grids``    — (D, mats, rows, cols) failure-probability grids
                             through the Pallas kernel (kernels/fail_prob.py,
                             dispatched by kernels/ops.py).
  * ``row_error_lambda``   — expected per-row error counts for the whole
                             population in one jitted call (Figs 6/7/14).
  * ``profile_population`` — DIVA / conventional profiling of every DIMM as a
                             single jitted program (Sec 6.1): an early-exit
                             loop down each timing grid; no Python loop over
                             DIMMs, subarrays or patterns.
  * ``lifetime_population`` — the whole online-profiling *lifecycle* (Sec 6.1
                             fn 2): one jitted ``lax.scan`` over profiling
                             epochs, applying host-precomputed aging-drift and
                             temperature-bin adders, re-running the DIVA sweep
                             each epoch, and emitting per-DIMM (timing,
                             stale-table failure, ECC-exposure) trajectories.

Monte-Carlo decisions use a counter-based hash (``query_uniform``) computed
identically by numpy (legacy per-DIMM path in core/errors.py) and jax (this
module), so the batched profiler reproduces the legacy walker bit-for-bit on
the uniform draws.  The profiling sweep itself uses fused jnp (regions are
reduction-dominated and tiny for DIVA); the Pallas kernel serves the
full-grid queries where the (mats, rows, cols) tensor is the product.

Every entry point takes ``mesh=``: a 1-D device mesh (``sharding.dimm_mesh``)
over which the DIMM axis is sharded via the ``sharding.shard_map`` shim.  The
hash RNG is keyed by each DIMM's global serial — which travels with its shard
— so sharding (and the padding that makes D divisible by the mesh) cannot
change any draw: sharded results are bit-identical to the single-device path.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import (DimmGeometry, precharge_delay,
                                 wordline_distance)
from repro.core.latency import (DEFAULT_ITERS, DEFAULT_PATTERNS,
                                PATTERN_STRESS, access_vdd_shift,
                                condition_scalars, fail_mixture, multibit_tail,
                                retention_fail_mixture, retention_stress,
                                worst_rows_internal)
from repro.core.timing import (AXES, CYCLE_NS, OP_GRID_LANE, PARAMS, STANDARD,
                               VDD_STD, OperatingPoint, TimingParams,
                               op_point_key)
from repro.obs import REGISTRY as _OBS_REGISTRY

if TYPE_CHECKING:  # avoid an import cycle: errors.py imports query_uniform
    from repro.core.errors import DimmModel

# Fixed sweep grids (Section 4 FPGA quantization) — static per axis, sourced
# from the AxisSpec registry (one definition, validated against the hash
# quantization at construction).  Timing keys stay {param: grid} for the
# legacy call sites; GRIDS covers every operating-point axis.
TIMING_GRIDS = {p: AXES[p].grid for p in PARAMS}
GRIDS = dict(TIMING_GRIDS, vdd=AXES["vdd"].grid, refresh=AXES["refresh"].grid)
# every timing parameter at once: the ``pidx`` of a full operating point
_TIMING_IDX = tuple(range(len(PARAMS)))


# ----------------------------------------------------------------- hashing

_GOLD = 0x9E3779B9


def _mix32(h, xp):
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * xp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def query_uniform(serial, param_idx, t_q, multibit, sub, pat, xp=np):
    """Deterministic uniform in [0, 1) for one Monte-Carlo profiling query.

    Pure function of (DIMM serial, timing parameter, quantized t_op, ECC
    criterion, subarray, pattern index) — the same bits from numpy and
    jax.numpy, so the legacy walker and the batched sweep agree exactly.
    Inputs broadcast; pass arrays (not 0-d scalars) on the numpy side.
    """
    u32 = lambda v: xp.asarray(v, xp.uint32)
    h = u32(serial) * xp.uint32(_GOLD)
    h = _mix32(h ^ (u32(param_idx) * xp.uint32(0x85EBCA6B)), xp)
    h = _mix32(h ^ (u32(t_q) * xp.uint32(0xC2B2AE35)), xp)
    h = _mix32(h ^ (u32(multibit) + u32(sub) * xp.uint32(0x27D4EB2F)
                    + u32(pat) * xp.uint32(0x165667B1)), xp)
    # top 24 bits -> exactly representable float32 in [0, 1)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def quantize_t(t_op) -> int:
    """The hash's t_op key: quarter-ns quantization (grid values are exact)."""
    return int(round(float(t_op) * 4))


def burst_uniform(seed, access, lane, xp=np):
    """Deterministic uniform in [0, 1) for one (access, burst-lane) error draw
    of the Fig 17 shuffling experiment — a sibling stream of ``query_uniform``
    (distinct mixing constants, so it never collides with profiling draws).

    Same bits from numpy (``shuffling.sample_chip_errors``) and jax
    (``shuffling_gain_population``); pass arrays, not 0-d scalars, on the
    numpy side.
    """
    u32 = lambda v: xp.asarray(v, xp.uint32)
    h = u32(seed) * xp.uint32(_GOLD)
    h = _mix32(h ^ (u32(access) * xp.uint32(0xB5297A4D)), xp)
    h = _mix32(h ^ (u32(lane) * xp.uint32(0x68E31DA4)), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def trace_uniform(seed, idx, lane, xp=np):
    """Deterministic uniform in [0, 1) for one per-request trace draw of the
    ramlite/memsim synthetic workloads — a sibling stream of ``query_uniform``
    / ``burst_uniform`` with fresh mixing constants (the global-index RNG
    rule): keyed by (workload stream seed, request index, draw lane), never by
    batch position, so stacking, sharding, and padding cannot change a trace.
    """
    u32 = lambda v: xp.asarray(v, xp.uint32)
    h = u32(seed) * xp.uint32(_GOLD)
    h = _mix32(h ^ (u32(idx) * xp.uint32(0xBF58476D)), xp)
    h = _mix32(h ^ (u32(lane) * xp.uint32(0x94D049BB)), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def fleet_uniform(seed, serial, lane, xp=np):
    """Deterministic uniform in [0, 1) for one synthetic-fleet leaf draw of
    ``population.synthetic_fleet`` — a sibling stream of ``query_uniform``
    with fresh mixing constants, keyed by (fleet seed, DIMM serial, leaf
    lane) and never by chunk position: a chunked fleet generator emits the
    same DIMM bits at any chunk size (the global-index RNG rule, applied to
    population *synthesis*)."""
    u32 = lambda v: xp.asarray(v, xp.uint32)
    h = u32(seed) * xp.uint32(_GOLD)
    h = _mix32(h ^ (u32(serial) * xp.uint32(0x2545F491)), xp)
    h = _mix32(h ^ (u32(lane) * xp.uint32(0x9E6D62D9)), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def mix_uniform(seed, draw, core, xp=np):
    """Deterministic uniform in [0, 1) for one multi-core workload-mix pick of
    ``ramlite.speedup_summary`` (Sec 6.3's 32 random mixes).  A dedicated hash
    stream with fresh mixing constants: the mixes no longer share
    ``default_rng(seed)`` state with trace seeding, so changing the trace
    configuration cannot silently reshuffle the mixes (and vice versa)."""
    u32 = lambda v: xp.asarray(v, xp.uint32)
    h = u32(seed) * xp.uint32(_GOLD)
    h = _mix32(h ^ (u32(draw) * xp.uint32(0xA0761D65)), xp)
    h = _mix32(h ^ (u32(core) * xp.uint32(0xE7037ED1)), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


# ------------------------------------------------------------- the batch

_LEAVES = ("serial", "base", "k_bl", "k_wl", "k_mat", "k_row", "sigma",
           "temp_coef", "refresh_coef", "aging_coef", "age_years",
           "outlier_rate", "outlier_ns", "chip_offsets", "sub_offsets",
           "row_src", "int_to_ext", "ext_to_int",
           "vdd_coef", "ret_base", "ret_k", "ret_sigma", "ret_drop")


@dataclass
class DimmBatch:
    """Stacked per-DIMM state; leading axis D on every leaf, geometry static.

    Coefficient tables are (D, 4) in ``timing.PARAMS`` order; ``row_src`` is
    the repair-resolved internal row source per (D, subarray, row) — repaired
    rows point at their replacement row, everything else at itself.
    """
    geom: DimmGeometry
    serial: Any          # (D,) uint32
    base: Any            # (D, 4) f32
    k_bl: Any            # (D, 4) f32
    k_wl: Any            # (D, 4) f32
    k_mat: Any           # (D, 4) f32
    k_row: Any           # (D, 4) f32
    sigma: Any           # (D,) f32
    temp_coef: Any       # (D,) f32
    refresh_coef: Any    # (D,) f32
    aging_coef: Any      # (D,) f32
    age_years: Any       # (D,) f32
    outlier_rate: Any    # (D,) f32
    outlier_ns: Any      # (D,) f32
    chip_offsets: Any    # (D, chips) f32
    sub_offsets: Any     # (D, subarrays) f32
    row_src: Any         # (D, subarrays, R) int32
    int_to_ext: Any      # (D, R) int32
    ext_to_int: Any      # (D, R) int32
    # operating-point axes beyond timing: access-channel voltage sensitivity
    # and the retention-channel margin model (see latency.VendorModel)
    vdd_coef: Any = None   # (D,) f32
    ret_base: Any = None   # (D,) f32
    ret_k: Any = None      # (D,) f32
    ret_sigma: Any = None  # (D,) f32
    ret_drop: Any = None   # (D,) f32

    @property
    def n_dimms(self) -> int:
        return int(self.serial.shape[0])

    def replace(self, **kw) -> "DimmBatch":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_population(cls, dimms: Sequence["DimmModel"]) -> "DimmBatch":
        """Stack DimmModels (all sharing one geometry) into array leaves."""
        if not dimms:
            raise ValueError("empty population: DimmBatch needs >= 1 DimmModel")
        geom = dimms[0].geom
        assert all(d.geom == geom for d in dimms), "mixed geometries in batch"
        R = geom.rows_per_mat
        rows = np.arange(R)
        f32 = lambda v: np.asarray(v, np.float32)

        def coeff(attr):
            return f32([[getattr(d.vendor, attr)[p] for p in PARAMS]
                        for d in dimms])

        row_src = np.stack([
            np.where(d.repaired, d.repair_perm, rows[None, :]) for d in dimms
        ]).astype(np.int32)
        return cls(
            geom=geom,
            serial=np.asarray([d.serial for d in dimms], np.uint32),
            base=coeff("base"), k_bl=coeff("k_bl"), k_wl=coeff("k_wl"),
            k_mat=coeff("k_mat"), k_row=coeff("k_row"),
            sigma=f32([d.vendor.sigma for d in dimms]),
            temp_coef=f32([d.vendor.temp_coef for d in dimms]),
            refresh_coef=f32([d.vendor.refresh_coef for d in dimms]),
            aging_coef=f32([d.vendor.aging_coef for d in dimms]),
            age_years=f32([d.age_years for d in dimms]),
            outlier_rate=f32([d.vendor.outlier_rate for d in dimms]),
            outlier_ns=f32([d.vendor.outlier_ns for d in dimms]),
            chip_offsets=f32([d.chip_offsets for d in dimms]),
            sub_offsets=f32([d.sub_offsets for d in dimms]),
            vdd_coef=f32([d.vendor.vdd_coef for d in dimms]),
            ret_base=f32([d.vendor.ret_base for d in dimms]),
            ret_k=f32([d.vendor.ret_k for d in dimms]),
            ret_sigma=f32([d.vendor.ret_sigma for d in dimms]),
            ret_drop=f32([d.vendor.ret_drop for d in dimms]),
            row_src=row_src,
            int_to_ext=np.stack([np.asarray(d.vendor.scramble.int_to_ext(rows))
                                 for d in dimms]).astype(np.int32),
            ext_to_int=np.stack([np.asarray(d.vendor.scramble.ext_to_int(rows))
                                 for d in dimms]).astype(np.int32),
        )


def _flatten(b: DimmBatch):
    return [getattr(b, n) for n in _LEAVES], b.geom


def _unflatten(geom, leaves):
    return DimmBatch(geom, *leaves)


jax.tree_util.register_pytree_node(DimmBatch, _flatten, _unflatten)


def pattern_stress(patterns=DEFAULT_PATTERNS) -> np.ndarray:
    return np.asarray([PATTERN_STRESS[p] for p in patterns], np.float32)


def _geom_consts(geom: DimmGeometry):
    """Static f32 distance tables shared by every DIMM (same die floorplan)."""
    C, M = geom.cols_per_mat, geom.mats_x
    d_wl = np.asarray(wordline_distance(geom, np.arange(C, dtype=np.float32)),
                      np.float32)
    d_mat = np.asarray(precharge_delay(geom, np.arange(M, dtype=np.float32)),
                       np.float32)
    even = (np.arange(C) % 2) == 0 if geom.open_bitline else np.ones(C, bool)
    return d_wl, d_mat, even


def condition_adders(batch: DimmBatch, temp_C: float,
                     refresh_ms: float) -> np.ndarray:
    """(D,) f32 operating-condition adders, computed HOST-side in numpy with
    the same op order as ``latency.condition_adder`` — the per-DIMM walker and
    the jitted sweep add literally identical bits (parity by construction,
    immune to XLA FMA contraction)."""
    t_delta, r_log = condition_scalars(temp_C, refresh_ms)
    return (np.asarray(batch.temp_coef, np.float32) * t_delta
            + np.asarray(batch.refresh_coef, np.float32) * r_log
            + np.asarray(batch.aging_coef, np.float32)
            * np.asarray(batch.age_years, np.float32))


# ------------------------------------------------- region failure decisions

def _region_eval(batch: DimmBatch, pidx, t_op, rows, stress,
                 adder, iters: int, multibit: bool, banks: int = 1,
                 extra=None, lane=None, key=None, ret_x=None):
    """Monte-Carlo region test of the whole batch at one operating point.

    Returns ``(fails, lam_total)``: (D, banks) bool — does the row region fail
    the test at t_op in each bank — and (D, banks) f32 — the expected failure
    count behind the accept/reject draws, summed over the bank's subarrays and
    patterns (the ECC-exposure integrand of the lifetime sweep when
    ``multibit=True``).  ``banks`` (static) partitions the subarray axis into
    equal contiguous groups — the per-bank profiling mode (FLY-DRAM-style
    bank heterogeneity); ``banks=1`` is the whole-DIMM reduction, and because
    each subarray's draws and float32 arithmetic are untouched by the
    grouping, it reproduces the pre-bank-axis results bit for bit.

    Mirrors ``DimmModel.region_has_errors`` op-for-op in float32; subarrays
    ride a lax.scan (memory), patterns/DIMMs are broadcast axes.  ``adder`` is
    the (D,) host-computed operating-condition term (condition_adders).
    ``t_op`` is a scalar (one grid point for everyone), a (D,) vector (the
    lifetime sweep testing each DIMM's own previous table), or a (D, S)
    per-subarray table (each bank's subarrays tested at that bank's own
    previous value); the hash sees the same per-DIMM bits in every layout.
    ``rows`` is a shared (Rr,) internal row region, or a per-DIMM (D, Rr)
    table — the blind-discovery pipeline tests each DIMM at its own recovered
    addresses.  The hash never keys on rows or banks, so two regions naming
    the same internal rows make identical draws.

    ``extra`` is an optional (D,) host-precomputed required-latency addend
    (the access-channel voltage shift of a non-nominal supply rail); its
    default ``None`` keeps the traced program literally identical to the
    pre-operating-point one — the same bit-parity trick as ``banks=1``.
    The hash never keys on conditions (temp/refresh/vdd context), so context
    changes move lambdas, never draws — the monotonicity sweeps lean on.

    A *full operating point* passes a tuple ``pidx`` of timing parameters
    with a (D, S, len(pidx)) ``t_op`` table: each parameter's access channel
    is tested at its own column, and the channel lambdas add into the one
    accept/reject draw per (subarray, pattern).  ``ret_x`` (a traced f32
    retention-stress scalar) adds the retention channel, driven by tRAS's
    variation sum.  The draw is keyed on ``(lane, key)``: by default the
    parameter and the quantized ``t_op``; the operating-point sweeps pass
    the swept axis's hash lane and quantized value (or the folded
    ``timing.op_point_key`` on ``OP_GRID_LANE``), so draws stay
    chunking/sharding invariant and single-axis sweeps monotone in lambda.
    """
    g = batch.geom
    R, S = g.rows_per_mat, g.subarrays
    assert S % banks == 0, (S, banks)
    subs_per_bank = S // banks
    chips = g.chips
    d_wl, d_mat, even = _geom_consts(g)

    pids = (pidx,) if isinstance(pidx, int) else tuple(pidx)
    coefs = [(batch.base[:, p], batch.k_bl[:, p], batch.k_wl[:, p],
              batch.k_mat[:, p], batch.k_row[:, p]) for p in pids]
    chip0 = batch.chip_offsets[:, 0]
    t_op = jnp.asarray(t_op, jnp.float32)
    if key is None:
        lane, key = pidx, jnp.round(t_op * 4).astype(jnp.uint32)
    else:
        key = jnp.asarray(key, jnp.uint32)
    t_cell_all = t_op[:, None, None, None, None] if t_op.ndim == 1 else t_op
    key_all = key[:, None] if key.ndim == 1 else key
    P = stress.shape[0]
    pat_idx = jnp.arange(P)[None, :]
    bank_ids = jnp.arange(banks)

    def channel_lam(pr):
        if multibit:
            return jnp.maximum(
                2 * iters * chips
                * multibit_tail(pr, xp=jnp).sum(axis=(2, 3, 4)) / 72.0, 0.0)
        return 2 * iters * chips * pr.sum(axis=(2, 3, 4))    # (D, P)

    def per_subarray(acc, s):
        fails_acc, lam_acc = acc
        if t_op.ndim == 3:                               # (D, S, n) tables
            t_cells = [t_op[:, s, k][:, None, None, None, None]
                       for k in range(len(pids))]
        elif t_op.ndim == 2:                             # (D, S) tables
            t_cells = [t_op[:, s][:, None, None, None, None]]
        else:
            t_cells = [t_cell_all]
        key_s = key[:, s][:, None] if key.ndim == 2 else key_all
        row_src_s = jnp.take(batch.row_src, s, axis=1)   # (D, R)
        if rows.ndim == 2:                               # per-DIMM regions
            rsel = jnp.take_along_axis(row_src_s, rows, axis=1)
        else:
            rsel = jnp.take(row_src_s, rows, axis=1)
        rf = rsel.astype(jnp.float32)                    # (D, Rr)
        d_bl = jnp.where(even[None, None, :], rf[:, :, None],
                         (R - 1) - rf[:, :, None]) / (R - 1)   # (D,Rr,C)
        d_row = rf / (R - 1)
        lam = var_tras = None
        for p, (base, kbl, kwl, kmat, krow), t_cell in zip(pids, coefs,
                                                          t_cells):
            var = (kbl[:, None, None, None] * d_bl[:, None, :, :]
                   + kwl[:, None, None, None] * d_wl[None, None, None, :]
                   + kmat[:, None, None, None] * d_mat[None, :, None, None]
                   + krow[:, None, None, None] * d_row[:, None, :, None])
            if p == 1:
                var_tras = var  # tRAS (charge restore) drives retention too
            t = base[:, None, None, None, None] \
                + stress[None, :, None, None, None] \
                * var[:, None, :, :, :]                  # (D,P,M,Rr,C)
            t = t + adder[:, None, None, None, None]
            if extra is not None:
                t = t + extra[:, None, None, None, None]
            t = t + chip0[:, None, None, None, None]
            t = t + jnp.take(batch.sub_offsets, s,
                             axis=1)[:, None, None, None, None]
            pr = fail_mixture(t, t_cell,
                              batch.sigma[:, None, None, None, None],
                              batch.outlier_rate[:, None, None, None, None],
                              batch.outlier_ns[:, None, None, None, None],
                              xp=jnp)
            lam = channel_lam(pr) if lam is None else lam + channel_lam(pr)
        if ret_x is not None:
            slow = stress[None, :, None, None, None] \
                * var_tras[:, None, :, :, :]
            pr = retention_fail_mixture(
                slow, batch.ret_base[:, None, None, None, None],
                batch.ret_k[:, None, None, None, None], ret_x,
                batch.ret_sigma[:, None, None, None, None],
                batch.outlier_rate[:, None, None, None, None],
                batch.ret_drop[:, None, None, None, None], xp=jnp)
            lam = lam + channel_lam(pr)
        u = query_uniform(batch.serial[:, None], lane, key_s, int(multibit),
                          s, pat_idx, xp=jnp)
        fail_s = jnp.any(u < -jnp.expm1(-lam), axis=1)   # (D,)
        bank_oh = bank_ids == s // subs_per_bank         # (banks,)
        fails_acc = fails_acc | (fail_s[:, None] & bank_oh[None, :])
        lam_acc = lam_acc + lam.sum(axis=1)[:, None] \
            * bank_oh.astype(jnp.float32)[None, :]
        return (fails_acc, lam_acc), None

    D = batch.serial.shape[0]
    init = (jnp.zeros((D, banks), bool), jnp.zeros((D, banks), jnp.float32))
    (fails, lam_total), _ = jax.lax.scan(per_subarray, init, jnp.arange(S))
    return fails, lam_total


def _sweep_param(batch: DimmBatch, pidx: int, floor, rows, stress, adder,
                 guard_cycles: int, iters: int, multibit: bool,
                 banks: int = 1, extra=None):
    """Walk one parameter's timing grid; per-(DIMM, bank) min-safe value
    (``floor`` is (D, banks)) and the number of grid points evaluated.

    Reproduces the legacy walker: stop at the first grid point that fails or
    undercuts the floor, keep the last safe value, add the guardband.  A
    table depends only on the grid prefix before each DIMM's first stop, so
    the ``lax.while_loop`` ends at the first point where every (DIMM, bank)
    has stopped or lies below its floor: the points it skips are the ones
    whose results a full scan would discard.
    """
    grid = jnp.asarray(GRIDS[PARAMS[pidx]], jnp.float32)
    std = getattr(STANDARD, PARAMS[pidx])
    G = grid.shape[0]

    def running(carry):
        i, stopped, _ = carry
        below = grid[jnp.minimum(i, G - 1)] < floor - 1e-9
        return (i < G) & ~jnp.all(stopped | below)

    def step(carry):
        i, stopped, best = carry
        t_op = grid[i]
        fail, _ = _region_eval(batch, pidx, t_op, rows, stress, adder,
                               iters, multibit, banks, extra)
        stopped = stopped | fail | (t_op < floor - 1e-9)
        best = jnp.where(stopped, best, jnp.minimum(best, t_op))
        return i + 1, stopped, best

    init = (jnp.int32(0), jnp.zeros(floor.shape, bool),
            jnp.full(floor.shape, jnp.inf, jnp.float32))
    n_steps, _, best = jax.lax.while_loop(running, step, init)
    best = jnp.where(jnp.isfinite(best), best, std)
    return jnp.minimum(best + guard_cycles * CYCLE_NS, std), n_steps


def _sweep_axis(batch: DimmBatch, axis: str, t_subs, rows, stress,
                extras_gd, adders_gd, keys_g, retx_g, guard_cycles: int,
                iters: int, multibit: bool, banks: int, retention: bool):
    """lax.scan along one NON-timing axis's grid (vdd / refresh): the
    per-(DIMM, bank) most aggressive safe value, everything else standard.

    Mirrors the paper's one-knob-at-a-time methodology: the axis is swept
    with the timing table at STANDARD values (``t_subs``), which also makes
    the bank-envelope property structural — a bank's stop points are a
    subset of the whole DIMM's, so per-bank values are never less
    aggressive than the whole-DIMM value.  The guardband retreats
    ``guard_cycles`` grid steps toward standard (the grid-step analogue of
    the timing sweep's ``guard_cycles * CYCLE_NS``); fewer safe points than
    the retreat means the standard value.
    """
    spec = AXES[axis]
    grid = jnp.asarray(spec.grid, jnp.float32)
    lane = spec.index

    def step(_, xs):
        extra_g, adder_g, key_g, retx = xs
        fail, _ = _region_eval(batch, _TIMING_IDX, t_subs, rows, stress,
                               adder_g, iters, multibit, banks, extra_g,
                               lane, key_g, retx if retention else None)
        return None, fail

    _, stops = jax.lax.scan(step, None,
                            (extras_gd, adders_gd, keys_g, retx_g))
    n_ok = jnp.sum(jnp.cumsum(stops.astype(jnp.int32), axis=0) == 0, axis=0)
    idx = n_ok - 1 - guard_cycles                        # (D, banks)
    vals = grid[jnp.clip(idx, 0, grid.shape[0] - 1)]
    return jnp.where(idx >= 0, vals, jnp.float32(spec.standard))


def _profile_impl(batch: DimmBatch, rows, stress, adder, ctx_d=None,
                  ctx_g=None, *, guard_cycles: int, iters: int,
                  multibit: bool, banks: int = 1, axes=PARAMS,
                  retention: bool = False):
    """The whole-population sweep: tRCD first, tRAS floored by tRCD + 10 ns
    (the Section 4 infrastructure constraint), then tRP and tWR — then any
    further operating-point axes (``axes`` beyond the mandatory 4-timing
    prefix: "vdd", "refresh"), each swept one-knob-at-a-time at standard
    timing via ``_sweep_axis``.  Returns (D, banks, len(axes)): per-bank
    tables when ``banks > 1`` (each bank's sweep sees only its own
    subarrays' failures, so a bank can settle below the whole-DIMM value —
    the FLY-DRAM margin), the whole-DIMM sweep at ``banks=1``.  Also returns
    (D, 4) int32: the grid points each timing parameter's loop evaluated,
    the same in every row (DIMM-leading so the padded and sharded routes
    slice it like the tables; ``sweep_steps`` reads it on the host).

    ``ctx_d``/``ctx_g`` carry the HOST-precomputed per-axis tables
    (``_axis_context``): ctx_d's leaves are DIMM-leading (sharded with the
    batch), ctx_g's are per-grid-point (replicated).  With the default
    ``axes=PARAMS``, no context and no retention, the traced program is
    bit-identical to the pre-operating-point 4-parameter sweep — the
    ``banks=1`` trick applied to the whole axis system.
    """
    assert tuple(axes[:len(PARAMS)]) == PARAMS, \
        f"axes must keep the 4 timing params as a prefix, got {axes!r}"
    D = batch.serial.shape[0]
    S = batch.geom.subarrays
    extra = None if not ctx_d else ctx_d.get("vdd_extra")
    kw = dict(rows=rows, stress=stress, adder=adder, banks=banks,
              guard_cycles=guard_cycles, iters=iters, multibit=multibit,
              extra=extra)
    floor5 = jnp.full((D, banks), 5.0, jnp.float32)
    res, steps = {}, {}
    res["trcd"], steps["trcd"] = _sweep_param(batch, 0, floor5, **kw)
    res["tras"], steps["tras"] = _sweep_param(batch, 1, res["trcd"] + 10.0,
                                              **kw)
    res["trp"], steps["trp"] = _sweep_param(batch, 2, floor5, **kw)
    res["twr"], steps["twr"] = _sweep_param(batch, 3, floor5, **kw)
    extra_axes = tuple(axes[len(PARAMS):])
    if extra_axes:
        std_t = jnp.asarray([getattr(STANDARD, p) for p in PARAMS],
                            jnp.float32)
        t_subs = jnp.broadcast_to(std_t[None, None, :], (D, S, len(PARAMS)))
        for ax in extra_axes:
            if ax == "vdd":
                extras_gd = ctx_d["vdd_shift"].T               # (G, D)
                adders_gd = jnp.broadcast_to(
                    adder[None, :], (extras_gd.shape[0], D))
            elif ax == "refresh":
                adders_gd = adder[None, :] + ctx_d["refresh_delta"].T
                base_extra = extra if extra is not None \
                    else jnp.zeros((D,), jnp.float32)
                extras_gd = jnp.broadcast_to(
                    base_extra[None, :], (adders_gd.shape[0], D))
            else:
                raise ValueError(f"unknown operating-point axis {ax!r}")
            res[ax] = _sweep_axis(
                batch, ax, t_subs, rows, stress, extras_gd, adders_gd,
                ctx_g[f"{ax}_keys"], ctx_g[f"{ax}_retx"], guard_cycles,
                iters, multibit, banks, retention)
    n_steps = jnp.stack([steps[p] for p in PARAMS])
    return (jnp.stack([res[a] for a in axes], axis=2),
            jnp.broadcast_to(n_steps[None, :], (D, len(PARAMS))))


_profile_jit = functools.partial(
    jax.jit, static_argnames=("guard_cycles", "iters", "multibit",
                              "banks", "axes", "retention"))(_profile_impl)


# ------------------------------------------------- DIMM-axis sharded dispatch

_SHARD_CACHE: dict = {}


def _mesh_key(mesh):
    return (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)


def _pad0(a, pad: int):
    """Pad dim 0 by repeating the last entry ``pad`` times.  Padding clones a
    real DIMM — its serial travels with it, so its (discarded) draws are that
    DIMM's and every kept DIMM's draws are untouched."""
    if pad == 0:
        return a
    if isinstance(a, jax.Array):  # device arrays / tracers stay on device
        return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)], axis=0)
    # host arrays pad in numpy: eager jnp here would compile (and cache) a
    # tiny XLA program PER (width, pad) shape — ~0.3 s of pure overhead the
    # first time each ragged-tail shape appears in a streaming scan
    a = np.asarray(a)
    return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)


def _run_sharded(name: str, mesh, impl, args, statics: dict,
                 batch_argnums: tuple):
    """Run ``impl(*args, **statics)`` under ``sharding.shard_map`` with dim 0
    of every ``batch_argnums`` arg (pytrees included) sharded over the mesh's
    single axis.  D is padded up to a multiple of the axis size and every
    output's dim 0 sliced back, so any population size runs on any mesh.
    Compiled programs are cached per (entry point, mesh, statics).
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import shard_map
    assert len(mesh.axis_names) == 1, "population meshes are 1-D (dimm axis)"
    axis = mesh.axis_names[0]
    n = int(mesh.devices.size)
    lead = jax.tree_util.tree_leaves(args[batch_argnums[0]])[0]
    D = int(lead.shape[0])
    pad = (-D) % n
    args = [jax.tree.map(lambda a: _pad0(a, pad), a) if i in batch_argnums
            else a for i, a in enumerate(args)]

    key = (name, _mesh_key(mesh), tuple(sorted(statics.items())),
           batch_argnums)
    prog = _SHARD_CACHE.get(key)
    if prog is None:
        _OBS_COMPILES.labels(cache="shard", entry=name).inc()
        in_specs = tuple(P(axis) if i in batch_argnums else P()
                         for i in range(len(args)))
        fn = functools.partial(impl, **statics)
        prog = _SHARD_CACHE[key] = jax.jit(
            shard_map(fn, mesh, in_specs=in_specs, out_specs=P(axis)))
    else:
        _OBS_REUSES.labels(cache="shard", entry=name).inc()
    out = prog(*args)
    return jax.tree.map(lambda a: a[:D], out)


def _dispatch(name: str, mesh, impl, jitted, args, statics: dict,
              batch_argnums: tuple):
    """One dispatch site for every substrate entry point: the cached jitted
    program when ``mesh`` is None, the shard_map route otherwise."""
    if mesh is None:
        return jitted(*args, **statics)
    return _run_sharded(name, mesh, impl, args, statics, batch_argnums)


# Compile-cache accounting (obs layer, ARCHITECTURE 3h): every program
# lowering and every cache reuse is counted by (cache, entry point), turning
# the one-compiled-program contract into a runtime metric — the streaming
# bench gate reads these counters instead of poking the cache dicts.
# Increments happen on the HOST at cache-decision time, never in traced code.
_OBS_COMPILES = _OBS_REGISTRY.counter(
    "repro_compile_programs_total",
    "XLA program lowerings by (cache, entry point)",
    labelnames=("cache", "entry"))
_OBS_REUSES = _OBS_REGISTRY.counter(
    "repro_compile_reuse_total",
    "compiled-program cache hits by (cache, entry point)",
    labelnames=("cache", "entry"))
# Sweep work: per call of a profiling program and per timing parameter, the
# grid points its early-exit loop evaluated and those it skipped (evaluated
# + skipped = the grid's length), read off the program's step output.
_OBS_SWEEP_STEPS = _OBS_REGISTRY.counter(
    "repro_sweep_grid_steps_total",
    "timing-grid points of the profiling sweep, evaluated or skipped",
    labelnames=("entry", "param", "outcome"))


# Campaign work: per call of a row-lambda program, the access channel's
# failure terms the row-sum kernel evaluated and those it skipped as
# exactly 0 or 1 (evaluated + saturated = twice the tiles times the mats),
# in (row block x column strip) tiles of one mat and one term, read off
# the program's count output.
_OBS_ERF_EVALS = _OBS_REGISTRY.counter(
    "repro_campaign_erf_evals_total",
    "failure terms of the row-sum fail_prob kernel, in tiles of one mat "
    "and one term, evaluated or skipped as saturated (exactly 0 or 1)",
    labelnames=("entry", "outcome"))


def erf_evals(entry: str, evals) -> None:
    """Count one row-lambda call's failure terms from its (D, 2)
    (evaluated, saturated) output, summed over every DIMM the kernel ran."""
    n = np.asarray(evals, np.int64).sum(axis=0)
    _OBS_ERF_EVALS.labels(entry=entry, outcome="evaluated").inc(int(n[0]))
    _OBS_ERF_EVALS.labels(entry=entry, outcome="saturated").inc(int(n[1]))


def sweep_steps(entry: str, steps) -> None:
    """Count one profiling call's grid points from ``_profile_impl``'s
    (D, 4) step output.  Every row holds its loop's count; under a mesh
    each shard ran its own loop, and the longest one is counted."""
    n = np.asarray(steps).max(axis=0)
    for p, k in zip(PARAMS, n):
        k, size = int(k), len(GRIDS[p])
        _OBS_SWEEP_STEPS.labels(entry=entry, param=p,
                                outcome="evaluated").inc(k)
        _OBS_SWEEP_STEPS.labels(entry=entry, param=p,
                                outcome="skipped").inc(size - k)


_CHUNK_JIT_CACHE: dict = {}


def donation_enabled() -> bool:
    """Buffer donation kill switch: ``REPRO_NO_DONATE=1`` makes every
    ``_chunk_jitted`` program non-donating (``donate_argnums=()``).  Results
    are bit-identical either way — donation only changes buffer lifetime —
    so this exists for A/B memory measurement (the streamed-scrub RSS
    regression test) and as an escape hatch if an XLA build mishandles
    aliasing.  Read at program-build time; the effective donate tuple keys
    the chunk cache, so flipping it mid-process compiles a separate program
    rather than corrupting a cached one."""
    return os.environ.get("REPRO_NO_DONATE", "0") != "1"


def _chunk_jitted(name: str, impl, statics: dict, donate: tuple):
    """Cached donating jit of one chunk program for the streaming driver
    (``core/streaming.py``).

    ``donate`` names the chunk-shaped positional args (the DimmBatch pytree
    and its per-chunk companions): their buffers are donated to XLA, so each
    chunk's arrays are released for reuse as soon as the program consumes
    them — the peak-memory lever of the streaming scan.  Shared args (row
    regions, pattern stress) are NEVER donated: the driver reuses them across
    every chunk.  The cache key is (entry point, statics, donate), i.e. one
    compiled program per chunk *shape*, reused for every chunk and every
    population size — the dense path re-lowers per population size instead.
    """
    if donation_enabled() is False:
        donate = ()
    key = (name, tuple(sorted(statics.items())), donate)
    prog = _CHUNK_JIT_CACHE.get(key)
    if prog is None:
        _OBS_COMPILES.labels(cache="chunk", entry=name).inc()
        prog = _CHUNK_JIT_CACHE[key] = jax.jit(
            functools.partial(impl, **statics), donate_argnums=donate)
    else:
        _OBS_REUSES.labels(cache="chunk", entry=name).inc()
    return prog


def _resolve_rows(region, geom: DimmGeometry, n_dimms: int | None = None
                  ) -> np.ndarray:
    """Region spec -> internal row indices: the named regions, a shared (Rr,)
    index array, or a per-DIMM (D, Rr) table (each DIMM tests its own rows —
    the blind-discovery mode)."""
    if isinstance(region, str):
        if region == "worst":
            return worst_rows_internal(geom)
        if region == "all":
            return np.arange(geom.rows_per_mat)
        raise ValueError(f"unknown region {region!r}; "
                         "use 'worst', 'all', or an index array")
    rows = np.asarray(region)
    if rows.ndim not in (1, 2):
        raise ValueError(f"region must be (rows,) or (dimms, rows); "
                         f"got shape {rows.shape}")
    if rows.ndim == 2 and n_dimms is not None and rows.shape[0] != n_dimms:
        raise ValueError(f"per-DIMM region has {rows.shape[0]} rows for "
                         f"{n_dimms} DIMMs")
    return rows


def _axis_context(batch: DimmBatch, axes, *, temp_C: float, refresh_ms: float,
                  vdd: float, np_out: bool = False):
    """HOST-precomputed per-axis tables for the generalized sweep — the
    ``lifetime_adders`` trick extended to the new axes: every
    operating-point-dependent float is computed in numpy f32 with the op
    order of the latency-module helpers, then fed into the jitted scan as
    data, never recomputed in-trace (parity with the numpy references by
    construction, immune to XLA fusion).

    Returns ``(ctx_d, ctx_g)``: DIMM-leading leaves (sharded with the
    batch; (D,) / (D, G) f32) and per-grid-point leaves (replicated; (G,)
    hash keys and retention stresses).  Both are ``None`` at the default
    operating point with no extra axes — the 4-arg bit-parity path.
    """
    ctx_d, ctx_g = {}, {}
    vc = np.asarray(batch.vdd_coef, np.float32)
    if vdd != VDD_STD:
        ctx_d["vdd_extra"] = access_vdd_shift(vc, vdd)
    if "vdd" in axes:
        spec = AXES["vdd"]
        ctx_d["vdd_shift"] = np.stack(
            [access_vdd_shift(vc, v) for v in spec.grid], axis=1)
        ctx_g["vdd_keys"] = np.asarray([spec.quantize(v) for v in spec.grid],
                                       np.uint32)
        ctx_g["vdd_retx"] = np.asarray(
            [retention_stress(temp_C, refresh_ms, v) for v in spec.grid],
            np.float32)
    if "refresh" in axes:
        spec = AXES["refresh"]
        base = condition_adders(batch, temp_C, refresh_ms)
        ctx_d["refresh_delta"] = np.stack(
            [condition_adders(batch, temp_C, r) - base for r in spec.grid],
            axis=1).astype(np.float32)
        ctx_g["refresh_keys"] = np.asarray(
            [spec.quantize(r) for r in spec.grid], np.uint32)
        ctx_g["refresh_retx"] = np.asarray(
            [retention_stress(temp_C, r, vdd) for r in spec.grid], np.float32)
    if not ctx_d and not ctx_g:
        return None, None
    if not np_out:
        ctx_d = {k: jnp.asarray(v) for k, v in ctx_d.items()}
        ctx_g = {k: jnp.asarray(v) for k, v in ctx_g.items()}
    return ctx_d, ctx_g


def profile_population_arrays(batch: DimmBatch, *, region: str = "worst",
                              temp_C: float = 55.0, refresh_ms: float = 64.0,
                              vdd: float = VDD_STD, guard_cycles: int = 1,
                              multibit_only: bool = False,
                              patterns=DEFAULT_PATTERNS,
                              iters: int = DEFAULT_ITERS,
                              banks: int = 1, axes=PARAMS,
                              retention: bool = False, mesh=None) -> np.ndarray:
    """(D, len(axes)) profiled operating values, one jitted call for all
    DIMMs; the first four columns are the timing table in PARAMS order.

    ``region="worst"`` is DIVA Profiling (the design-induced slowest rows);
    ``region="all"`` is conventional every-row profiling; a (D, Rr) array
    gives every DIMM its own internal test rows (blind discovery).
    ``banks > 1`` partitions the subarray axis into that many equal bank
    groups and returns per-bank tables, shape (D, banks, 4): each bank is
    profiled against only its own subarrays, so its table is <= the
    whole-DIMM table entry-wise (the bank-heterogeneity margin FLY-DRAM
    exploits); ``banks=1`` (the whole-DIMM reduction) stays (D, 4) and
    bit-identical to the pre-bank-axis results.  ``mesh`` shards the DIMM
    axis over a 1-D device mesh (``sharding.dimm_mesh``) — bit-identical to
    the single-device path.

    ``axes`` extends the sweep beyond the mandatory 4-timing prefix with
    operating-point axes ("vdd", "refresh" — see ``timing.AXES``), each
    swept one-knob-at-a-time at standard timing (the paper's methodology
    generalized); ``vdd`` sets the *ambient* supply context for the timing
    sweeps, and ``retention`` adds the refresh/temperature-driven retention
    error channel to the non-timing axis evaluations.  The default
    (``axes=PARAMS``, nominal vdd, no retention) traces the pre-refactor
    program bit for bit.
    """
    if batch.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={batch.geom.subarrays}")
    axes = tuple(axes)
    rows = _resolve_rows(region, batch.geom, batch.n_dimms)
    adder = condition_adders(batch, temp_C, refresh_ms)
    ctx_d, ctx_g = _axis_context(batch, axes, temp_C=temp_C,
                                 refresh_ms=refresh_ms, vdd=vdd)
    args = (batch, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pattern_stress(patterns)), jnp.asarray(adder))
    # a per-DIMM region is batch-shaped: shard it with the DIMM axis
    argnums = (0, 1, 3) if rows.ndim == 2 else (0, 3)
    if ctx_d is not None:
        args = args + (ctx_d, ctx_g)
        argnums = argnums + (4,)
    statics = dict(guard_cycles=guard_cycles, iters=iters,
                   multibit=multibit_only, banks=banks, axes=axes,
                   retention=retention)
    out, steps = _dispatch("profile", mesh, _profile_impl, _profile_jit,
                           args, statics, batch_argnums=argnums)
    out = np.asarray(out)
    sweep_steps("profile", steps)
    return out[:, 0] if banks == 1 else out


def profile_population(batch: DimmBatch, **kw) -> list[TimingParams]:
    """Per-DIMM ``TimingParams`` for the whole population (see arrays variant).

    With extended ``axes`` only the 4-timing prefix lands in TimingParams;
    use the arrays variant (or ``operating_points_population``) for the
    full rows.
    """
    arr = profile_population_arrays(batch, **kw)
    return [TimingParams(*(float(v) for v in row[:len(PARAMS)]))
            for row in arr]


def operating_points_population(batch: DimmBatch, *, temp_C: float = 55.0,
                                vdd: float = VDD_STD, **kw
                                ) -> list[OperatingPoint]:
    """Per-DIMM ``OperatingPoint`` over the full extended axis list: the
    timing table plus the per-DIMM min-safe vdd and max-safe refresh
    interval (each profiled one-knob-at-a-time; see arrays variant)."""
    from repro.core.timing import EXTENDED_AXES
    kw.setdefault("axes", EXTENDED_AXES)
    kw.setdefault("retention", True)
    arr = profile_population_arrays(batch, temp_C=temp_C, vdd=vdd, **kw)
    axes = tuple(kw["axes"])
    out = []
    for row in arr:
        d = dict(zip(axes, (float(v) for v in row)))
        out.append(OperatingPoint(
            timing=TimingParams(*(d[p] for p in PARAMS)),
            vdd=d.get("vdd", vdd), temp_C=temp_C,
            refresh_ms=d.get("refresh", 64.0)))
    return out


# --------------------------------------------- lifetime sweeps (Sec 6.1 fn 2)

def lifetime_adders(batch: DimmBatch, ages, temps,
                    refresh_ms: float = 64.0) -> np.ndarray:
    """(E, D) f32 per-epoch operating-condition adders, HOST-side in numpy
    with the op order of ``latency.condition_adder`` — the per-DIMM Python
    lifecycle (``profiling.lifetime_loop``) and the jitted epoch scan add
    literally identical bits (parity by construction, immune to XLA fusion).

    ``ages`` / ``temps``: per-epoch (E,) or per-epoch-per-DIMM (E, D) values;
    ``ages`` *overrides* the batch's static ``age_years`` leaf — the epoch
    schedule owns the drift.
    """
    D = batch.n_dimms
    ages = np.asarray(ages, np.float32)
    temps = np.asarray(temps, np.float64)
    if ages.ndim == 1:
        ages = np.broadcast_to(ages[:, None], (ages.shape[0], D))
    if temps.ndim == 1:
        temps = np.broadcast_to(temps[:, None], (temps.shape[0], D))
    if not (ages.shape == temps.shape == (ages.shape[0], D)):
        raise ValueError(f"ages {ages.shape} / temps {temps.shape} must both "
                         f"resolve to (n_epochs, {D})")
    t_delta = np.float32(temps - 85.0)
    _, r_log = condition_scalars(85.0, refresh_ms)
    tc = np.asarray(batch.temp_coef, np.float32)[None, :]
    rc = np.asarray(batch.refresh_coef, np.float32)[None, :]
    ac = np.asarray(batch.aging_coef, np.float32)[None, :]
    return tc * t_delta + rc * r_log + ac * ages


def _lifetime_impl(batch: DimmBatch, rows, stress, adders_dl, ctx_d=None,
                   ctx_g=None, *, guard_cycles: int, iters: int,
                   multibit: bool, diagnostics: bool, banks: int = 1,
                   axes=PARAMS, retention: bool = False):
    """One ``lax.scan`` over profiling epochs.  ``adders_dl`` is (D, E) —
    DIMM-leading so the sharded runner can split dim 0 like every other arg;
    the scan walks the epoch axis.

    Each epoch re-runs the full DIVA sweep under that epoch's conditions;
    with ``diagnostics`` it additionally reports, per (DIMM, bank):
      * ``stale``: would the PREVIOUS epoch's table (the standard table at
        epoch 0) now fail the region test — the aging-drift unsafety that
        static AL-DRAM-style tables accumulate (Sec 6.1 fn 2);
      * ``ecc``: expected SECDED-multi-bit codewords of the region test at
        the freshly profiled point — the residual ECC exposure DIVA+ECC
        carries at its operating point.
    Without it the epoch body is just the sweep — what the timing-only
    wrappers (ALDRAM.install, DivaProfiler) pay for.

    ``banks > 1`` threads the per-bank table axis through the whole
    lifecycle: each epoch profiles (D, banks, 4) tables and the stale test
    evaluates every bank's subarrays at that bank's own previous value.

    Returns DIMM-leading trajectories: (D, E, banks, len(axes)), (D, E,
    banks) bool, (D, E, banks) f32 — or only the timings when
    ``diagnostics`` is off.  With extended ``axes`` every epoch re-sweeps
    the non-timing axes too (the per-axis context tables are
    epoch-constant); the stale/ECC diagnostics keep evaluating the 4-timing
    prefix — the staleness the Sec 6.1 argument is about.
    """
    D = batch.serial.shape[0]
    S = batch.geom.subarrays
    sub_bank = jnp.asarray(np.arange(S) // (S // banks), jnp.int32)
    std = jnp.asarray([AXES[a].standard for a in axes], jnp.float32)
    extra = None if not ctx_d else ctx_d.get("vdd_extra")
    kw = dict(rows=rows, stress=stress, guard_cycles=guard_cycles,
              iters=iters, multibit=multibit, banks=banks,
              ctx_d=ctx_d, ctx_g=ctx_g, axes=axes, retention=retention)

    def epoch(prev_t, adder):
        t_new, _ = _profile_impl(batch, adder=adder, **kw)  # (D, banks, n_axes)
        if not diagnostics:
            return t_new, (t_new,)
        stale = jnp.zeros((D, banks), bool)
        ecc = jnp.zeros((D, banks), jnp.float32)
        for p in range(len(PARAMS)):
            # each subarray is tested at ITS bank's table value: expand the
            # (D, banks) per-bank column to a (D, S) per-subarray table
            # (for banks=1 this carries the same per-DIMM values as before,
            # so every draw and decision is unchanged)
            prev_s = jnp.take(prev_t[:, :, p], sub_bank, axis=1)
            fail_p, _ = _region_eval(batch, p, prev_s, rows, stress,
                                     adder, iters, multibit, banks, extra)
            stale = stale | fail_p
            new_s = jnp.take(t_new[:, :, p], sub_bank, axis=1)
            _, lam_p = _region_eval(batch, p, new_s, rows, stress,
                                    adder, iters, True, banks, extra)
            ecc = ecc + lam_p
        return t_new, (t_new, stale, ecc)

    init = jnp.broadcast_to(std, (D, banks, len(axes)))
    _, ys = jax.lax.scan(epoch, init, adders_dl.T)
    return tuple(jnp.moveaxis(y, 0, 1) for y in ys)


_lifetime_jit = functools.partial(
    jax.jit, static_argnames=("guard_cycles", "iters", "multibit",
                              "diagnostics", "banks", "axes",
                              "retention"))(_lifetime_impl)


def lifetime_population(batch: DimmBatch, ages, temps, *,
                        refresh_ms: float = 64.0, vdd: float = VDD_STD,
                        region: str = "worst",
                        guard_cycles: int = 1, multibit: bool = True,
                        patterns=DEFAULT_PATTERNS, iters: int = DEFAULT_ITERS,
                        diagnostics: bool = True, banks: int = 1,
                        axes=PARAMS, retention: bool = False,
                        mesh=None) -> dict:
    """The whole online re-profiling lifecycle as ONE device program.

    ``ages`` / ``temps`` give each profiling epoch's operating point ((E,) or
    (E, D)); every epoch re-runs the DIVA sweep under drifted conditions —
    the Sec 6.1 argument for *online* profiling, and the drift that makes
    static AL-DRAM tables unsafe.  Epoch-by-epoch timing decisions are
    bit-identical to the retained Python reference
    (``profiling.lifetime_loop``) via the shared per-query hash.

    Returns epoch-leading arrays: ``timings`` (E, D, 4) ns in PARAMS order,
    ``stale_fail`` (E, D) bool (previous epoch's table — standard at epoch 0
    — now fails the region test), ``ecc_lambda`` (E, D) expected multi-bit
    codewords at the fresh operating point, plus the resolved (E, D)
    ``ages``/``temps`` schedule.  ``banks > 1`` threads per-bank tables
    through every epoch (see ``profile_population_arrays``): ``timings``
    becomes (E, D, banks, 4) and the diagnostics (E, D, banks), with each
    bank's stale test run at that bank's own previous value.
    ``diagnostics=False`` skips the stale/ECC evaluations (and their keys) —
    the cheap timing-only mode the ALDRAM / DivaProfiler wrappers use.
    ``mesh`` shards the DIMM axis.  ``axes``/``vdd``/``retention`` extend
    each epoch's sweep to the full operating-point space (see
    ``profile_population_arrays``); ``timings`` then carries len(axes)
    columns per epoch.
    """
    if batch.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={batch.geom.subarrays}")
    axes = tuple(axes)
    rows = _resolve_rows(region, batch.geom, batch.n_dimms)
    adders = lifetime_adders(batch, ages, temps, refresh_ms)     # (E, D)
    # the per-axis context is epoch-constant: refresh deltas and vdd shifts
    # don't depend on the age/temperature schedule (temp and age terms
    # cancel in the refresh delta)
    ctx_d, ctx_g = _axis_context(batch, axes, temp_C=85.0,
                                 refresh_ms=refresh_ms, vdd=vdd)
    args = (batch, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pattern_stress(patterns)), jnp.asarray(adders.T))
    argnums = (0, 1, 3) if rows.ndim == 2 else (0, 3)
    if ctx_d is not None:
        args = args + (ctx_d, ctx_g)
        argnums = argnums + (4,)
    statics = dict(guard_cycles=guard_cycles, iters=iters, multibit=multibit,
                   diagnostics=diagnostics, banks=banks, axes=axes,
                   retention=retention)
    out = _dispatch("lifetime", mesh, _lifetime_impl, _lifetime_jit, args,
                    statics, batch_argnums=argnums)
    # drop the bank axis in whole-DIMM mode (timings (D,E,1,4) -> (D,E,4))
    sq = (lambda a: a[:, :, 0]) if banks == 1 else (lambda a: a)
    out = [np.asarray(sq(v)) for v in out]
    E, D = adders.shape
    # the resolved schedule replays bit-identically: ages are consumed as
    # f32, temps as f64 — echo each at its consumed precision
    to_ed = lambda v, dt: np.broadcast_to(
        np.asarray(v, dt).reshape((E, -1)), (E, D)).copy()
    res = {"timings": np.moveaxis(out[0], 0, 1),
           "ages": to_ed(ages, np.float32), "temps": to_ed(temps, np.float64)}
    if diagnostics:
        res["stale_fail"] = np.moveaxis(out[1], 0, 1)
        res["ecc_lambda"] = np.moveaxis(out[2], 0, 1)
    return res


# ------------------------------------------- operating-grid sweeps (N-axis)

def operating_grid_tables(batch: DimmBatch, points) -> tuple:
    """HOST-side tables for a static grid of ``OperatingPoint``s.

    Returns ``(t_g, adders_dg, shifts_dg, keys_g, retx_g)``: per-point
    timing rows (G, 4) f32, per-DIMM condition adders and voltage shifts
    (D, G) f32 (DIMM-leading, sharded with the batch), and per-point hash
    keys (G,) uint32 / retention stresses (G,) f32.  Keys fold the
    quantized timing/vdd/refresh coordinates via ``timing.op_point_key`` —
    conditions (temperature) never key a draw.
    """
    t_g = np.asarray([[getattr(pt.timing, p) for p in PARAMS]
                      for pt in points], np.float32)
    adders_dg = np.stack([condition_adders(batch, pt.temp_C, pt.refresh_ms)
                          for pt in points], axis=1).astype(np.float32)
    vc = np.asarray(batch.vdd_coef, np.float32)
    shifts_dg = np.stack([access_vdd_shift(vc, pt.vdd) for pt in points],
                         axis=1)
    keys = []
    for pt in points:
        tq = 0
        for p in PARAMS:
            tq = (tq * 0x9E3779B9 + AXES[p].quantize(getattr(pt.timing, p))) \
                & 0xFFFFFFFF
        keys.append(op_point_key(tq, AXES["vdd"].quantize(pt.vdd),
                                 AXES["refresh"].quantize(pt.refresh_ms)))
    keys_g = np.asarray(keys, np.uint32)
    retx_g = np.asarray([retention_stress(pt.temp_C, pt.refresh_ms, pt.vdd)
                         for pt in points], np.float32)
    return t_g, adders_dg, shifts_dg, keys_g, retx_g


def _op_grid_impl(batch: DimmBatch, rows, stress, t_g, adders_dg, shifts_dg,
                  keys_g, retx_g, *, iters: int, multibit: bool,
                  banks: int = 1, retention: bool = True):
    """lax.scan over a static operating-point grid: per point, the full
    two-channel region evaluation of every DIMM (``_region_eval`` over all
    four timing parameters).
    Returns ``(fails, lam)`` shaped (D, G, banks) — per-DIMM results are
    independent across points (no sweep/stop logic), so the scan carries
    no state and chunk/shard partitions of D commute with it.
    """
    D = batch.serial.shape[0]
    S = batch.geom.subarrays

    def point(_, xs):
        t_pt, adder_g, shift_g, key_g, retx = xs
        t_subs = jnp.broadcast_to(t_pt[None, None, :], (D, S, len(PARAMS)))
        return None, _region_eval(batch, _TIMING_IDX, t_subs, rows, stress,
                                  adder_g, iters, multibit, banks, shift_g,
                                  OP_GRID_LANE, key_g,
                                  retx if retention else None)

    xs = (t_g, adders_dg.T, shifts_dg.T, keys_g, retx_g)
    _, (fails, lam) = jax.lax.scan(point, None, xs)      # (G, D, banks)
    return jnp.moveaxis(fails, 0, 1), jnp.moveaxis(lam, 0, 1)


_op_grid_jit = functools.partial(
    jax.jit, static_argnames=("iters", "multibit", "banks",
                              "retention"))(_op_grid_impl)


def operating_grid_arrays(batch: DimmBatch, points, *,
                          region: str = "worst",
                          patterns=DEFAULT_PATTERNS,
                          iters: int = DEFAULT_ITERS,
                          multibit_only: bool = False, banks: int = 1,
                          retention: bool = True, mesh=None) -> dict:
    """Evaluate every DIMM at every ``OperatingPoint`` in ``points`` — the
    batched N-axis (timing x voltage x temperature x refresh) sweep.

    One jitted scan over the G grid points; returns ``fails`` (D, G[, banks])
    bool Monte-Carlo region outcomes and ``lam`` (D, G[, banks]) f32
    expected failure counts (access + retention channels).  The per-point
    loop reference is ``DimmModel.operating_point_eval``; parity holds
    decision-for-decision via the shared counter hash (lam to float32
    reduction tolerance).  ``mesh`` shards the DIMM axis.
    """
    if batch.geom.subarrays % banks != 0:
        raise ValueError(f"banks={banks} must divide "
                         f"subarrays={batch.geom.subarrays}")
    rows = _resolve_rows(region, batch.geom, batch.n_dimms)
    t_g, adders_dg, shifts_dg, keys_g, retx_g = \
        operating_grid_tables(batch, points)
    args = (batch, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pattern_stress(patterns)), jnp.asarray(t_g),
            jnp.asarray(adders_dg), jnp.asarray(shifts_dg),
            jnp.asarray(keys_g), jnp.asarray(retx_g))
    statics = dict(iters=iters, multibit=multibit_only, banks=banks,
                   retention=retention)
    argnums = (0, 1, 4, 5) if rows.ndim == 2 else (0, 4, 5)
    fails, lam = _dispatch("op_grid", mesh, _op_grid_impl, _op_grid_jit,
                           args, statics, batch_argnums=argnums)
    sq = (lambda a: a[..., 0]) if banks == 1 else (lambda a: a)
    return {"fails": np.asarray(sq(fails)), "lam": np.asarray(sq(lam))}


# --------------------------------------------------- full-grid batched API

def _pack_coeffs(batch: DimmBatch, pidx: int, t_op, stress, adder,
                 chip, sub_idx, shift=None, ret_x=None):
    """(D, 9) folded per-DIMM coefficient rows for the fail_prob kernel;
    ``adder`` is the host-computed (D,) operating-condition term.  With a
    (D,) voltage ``shift``, the (D, 15) operating-point rows: the supply
    shift and the retention channel (ret_base, ret_k, the scalar retention
    stress ``ret_x``, ret_sigma, ret_drop) follow the 9."""
    base_eff = (batch.base[:, pidx] + adder + batch.chip_offsets[:, chip]
                + jnp.take(batch.sub_offsets, sub_idx, axis=1))
    cols = [
        base_eff, stress * batch.k_bl[:, pidx], stress * batch.k_wl[:, pidx],
        stress * batch.k_mat[:, pidx], stress * batch.k_row[:, pidx],
        jnp.full_like(base_eff, t_op), batch.sigma, batch.outlier_rate,
        batch.outlier_ns,
    ]
    if shift is not None:
        cols += [jnp.asarray(shift, jnp.float32), batch.ret_base, batch.ret_k,
                 jnp.full_like(batch.ret_base, np.float32(ret_x)),
                 batch.ret_sigma, batch.ret_drop]
    return jnp.stack(cols, axis=1).astype(jnp.float32)


def _fail_prob_impl(row_src, d_mat, coeffs, *, cols: int, pallas: bool):
    from repro.kernels import ops
    return ops.fail_prob_batch(row_src, d_mat, coeffs, cols=cols,
                               pallas=pallas)


# the unsharded route is jitted too, so the jnp oracle (REPRO_FORCE_REF)
# compiles identically with and without a mesh — eager jnp fuses differently
# and would cost the sharded paths their bit-parity
_fail_prob_jit = functools.partial(
    jax.jit, static_argnames=("cols", "pallas"))(_fail_prob_impl)


def fail_prob_grids(batch: DimmBatch, param: str, t_op: float, *,
                    temp_C: float = 85.0, refresh_ms: float = 64.0,
                    pattern: str = "0101", chip: int = 0,
                    subarray: int = 0, mesh=None) -> jnp.ndarray:
    """(D, mats, rows, cols) failure-probability grids for every DIMM — the
    batched sibling of ``DimmModel.fail_prob_grid``, computed by the Pallas
    kernel (or its jnp oracle under REPRO_FORCE_REF).  ``mesh`` shards the
    DIMM axis."""
    from repro.kernels import ops
    pidx = PARAMS.index(param)
    adder = condition_adders(batch, temp_C, refresh_ms)
    stress = np.float32(PATTERN_STRESS[pattern])
    coeffs = _pack_coeffs(batch, pidx, np.float32(t_op), stress,
                          jnp.asarray(adder), chip, subarray)
    row_src = batch.row_src[:, subarray]
    _, d_mat, _ = _geom_consts(batch.geom)
    statics = dict(cols=batch.geom.cols_per_mat, pallas=ops.use_pallas())
    return _dispatch("fail_prob", mesh, _fail_prob_impl, _fail_prob_jit,
                     (jnp.asarray(row_src), jnp.asarray(d_mat), coeffs),
                     statics, batch_argnums=(0, 2))


def _row_lambda_impl(batch: DimmBatch, t_op, stress, adder, *,
                     pidx: int, iters: int, internal: bool, pallas: bool):
    from repro.kernels import ops
    g = batch.geom
    S, P = g.subarrays, stress.shape[0]
    _, d_mat, _ = _geom_consts(g)
    d_mat = jnp.asarray(d_mat)
    row_sums = functools.partial(ops.fail_prob_batch, cols=g.cols_per_mat,
                                 reduce_rows=True, pallas=pallas)  # over DIMMs
    row_evals = functools.partial(ops.fail_prob_erf_evals,
                                  cols=g.cols_per_mat, pallas=pallas)
    D, R = batch.serial.shape[0], g.rows_per_mat

    def per_subarray(evals, s):
        def per_pattern(carry, pi):
            acc_p, evals = carry
            coeffs = _pack_coeffs(batch, pidx, t_op, stress[pi], adder, 0, s)
            row_src = jnp.take(batch.row_src, s, axis=1)
            sums = row_sums(row_src, d_mat, coeffs)
            evals = evals + row_evals(row_src, d_mat, coeffs)
            return (acc_p + 2 * sums * g.chips, evals), None
        (exp_row, evals), _ = jax.lax.scan(
            per_pattern, (jnp.zeros((D, R), jnp.float32), evals),
            jnp.arange(P))
        return evals, exp_row * iters                    # (D, R) per subarray

    evals, lam = jax.lax.scan(per_subarray, jnp.zeros((D, 2), jnp.int32),
                              jnp.arange(S))             # (S, D, R)
    lam = jnp.moveaxis(lam, 0, 1)                        # (D, S, R)
    if not internal:
        # counts are produced in internal order then scattered to external
        # addressing: ext_counts[j] = counts[ext_to_int[j]]
        lam = jnp.take_along_axis(lam, batch.ext_to_int[:, None, :]
                                  .repeat(lam.shape[1], axis=1), axis=2)
    return lam.reshape(lam.shape[0], -1), evals


_row_lambda_jit = functools.partial(
    jax.jit, static_argnames=("pidx", "iters", "internal",
                              "pallas"))(_row_lambda_impl)


def row_error_lambda(batch: DimmBatch, param: str, t_op: float, *,
                     temp_C: float = 85.0, refresh_ms: float = 64.0,
                     patterns=DEFAULT_PATTERNS, iters: int = DEFAULT_ITERS,
                     internal_order: bool = False, mesh=None) -> np.ndarray:
    """(D, subarrays*rows) expected error counts per row address for every
    DIMM — the population-scale ``row_error_counts(sample=False)``.  ``mesh``
    shards the DIMM axis."""
    from repro.kernels import ops
    adder = condition_adders(batch, temp_C, refresh_ms)
    args = (batch, np.float32(t_op), jnp.asarray(pattern_stress(patterns)),
            jnp.asarray(adder))
    statics = dict(pidx=PARAMS.index(param), iters=iters,
                   internal=internal_order, pallas=ops.use_pallas())
    lam, evals = _dispatch("row_lambda", mesh, _row_lambda_impl,
                           _row_lambda_jit, args, statics,
                           batch_argnums=(0, 3))
    erf_evals("row_lambda", evals)
    return np.asarray(lam)


# ----------------------------------------------- batched DIVA Shuffling (Fig 17)

N_LANES = 9 * 64  # chips x burst bits, the SECDED burst of core/shuffling.py


def _shuffling_impl(probs, seeds, *, n_accesses: int, pallas: bool):
    """The whole Fig 17 experiment as one program: sample (D, n, 9, 64) error
    tensors with the counter-hash RNG, lay the lanes out per codeword with and
    without DIVA Shuffling (kernels/shuffle permutation matmul), and score
    every codeword (kernels/secded syndrome + error weight).

    ``pallas`` is the dispatch mode resolved OUTSIDE the jit (REPRO_FORCE_REF)
    — as a static arg it keys the cache, so toggling the env var between
    same-shape calls retraces instead of silently reusing the other path.
    """
    from repro.kernels import ops
    D = probs.shape[0]
    acc = jnp.arange(n_accesses, dtype=jnp.uint32)
    lane = jnp.arange(N_LANES, dtype=jnp.uint32)
    u = burst_uniform(seeds[:, None, None], acc[None, :, None],
                      lane[None, None, :], xp=jnp)          # (D, n, 576)
    errs = (u < probs.reshape(D, 1, N_LANES)).astype(jnp.int32)
    flat = errs.reshape(D * n_accesses, N_LANES)
    if pallas:
        # Interpret mode (CPU) pays per-grid-step overhead, so run each
        # kernel as one full-array tile there; on TPU keep the VMEM-sized
        # default tiles.
        tile = flat.shape[0] if ops.interpret_mode() else None
        shuffle_fn = functools.partial(ops.diva_shuffle, tile=tile)
        syndrome_fn = functools.partial(
            ops.secded_syndrome, tile=None if tile is None else 2 * 8 * tile)
    else:
        from repro.kernels import ref
        shuffle_fn = ref.diva_shuffle
        syndrome_fn = ref.secded_syndrome

    # (beat, chip, dq) layout -> 8 codeword masks of 72 bits per access
    masks_ns = shuffle_fn(flat, shuffle=False)
    masks_s = shuffle_fn(flat, shuffle=True)
    both = jnp.stack([masks_ns, masks_s]).reshape(2, D, n_accesses * 8, 72)
    w = both.sum(axis=3)                                    # per-codeword weight
    syn = syndrome_fn(both.reshape(-1, 72))
    detected = jnp.any(syn.reshape(2, D, n_accesses * 8, 8) > 0, axis=3)
    corrected = jnp.where(w == 1, w, 0).sum(axis=2)          # (2, D)
    uncorrectable = (w > 1).sum(axis=2)
    undetected = ((w > 1) & ~detected).sum(axis=2)           # silent corruption
    total = errs.sum(axis=(1, 2))
    return (total, corrected[0], corrected[1], uncorrectable[0],
            uncorrectable[1], undetected[0], undetected[1])


_shuffling_jit = functools.partial(
    jax.jit, static_argnames=("n_accesses", "pallas"))(_shuffling_impl)


def shuffling_gain_population(bit_error_prob, *, seeds=None, seed: int = 0,
                              n_accesses: int = 2000, mesh=None) -> dict:
    """Fig 17 at population scale: per-DIMM correctable-error fractions with
    and without DIVA Shuffling, for (D, 9, 64) burst-bit error profiles (from
    ``burst_bit_profile_population`` or synthetic), in one jitted call.

    ``seeds`` gives each DIMM its error-draw stream (default ``seed + i``);
    on a singleton batch with the same seed this reproduces
    ``shuffling.shuffling_gain_loop`` count-for-count (shared counter hash).
    Beyond the loop's counts it reports uncorrectable and *undetected*
    (syndrome-aliased multi-bit) codewords per mode via the SECDED syndrome
    kernel.  ``mesh`` shards the DIMM axis (each DIMM's draws are keyed by
    its own seed, so sharding cannot change them).
    """
    probs = np.asarray(bit_error_prob, np.float32)
    if probs.ndim == 2:
        probs = probs[None]
    assert probs.shape[1:] == (9, 64), probs.shape
    D = probs.shape[0]
    if seeds is None:
        seeds = seed + np.arange(D)
    seeds = np.asarray(seeds, np.uint32)
    assert seeds.shape == (D,)
    from repro.kernels import ops
    statics = dict(n_accesses=n_accesses, pallas=ops.use_pallas())
    out = _dispatch("shuffling", mesh, _shuffling_impl, _shuffling_jit,
                    (jnp.asarray(probs), jnp.asarray(seeds)), statics,
                    batch_argnums=(0, 1))
    total, c_ns, c_s, unc_ns, unc_s, und_ns, und_s = (
        np.asarray(v, np.int64) for v in out)
    denom = np.maximum(total, 1)
    return {"total": total,
            "frac_no_shuffle": np.where(total == 0, 1.0, c_ns / denom),
            "frac_shuffle": np.where(total == 0, 1.0, c_s / denom),
            "gain": np.where(total == 0, 0.0, (c_s - c_ns) / denom),
            "uncorrectable_no_shuffle": unc_ns, "uncorrectable_shuffle": unc_s,
            "undetected_no_shuffle": und_ns, "undetected_shuffle": und_s}


def burst_bit_profile_population(batch: DimmBatch, param: str, t_op: float, *,
                                 temp_C: float = 85.0, refresh_ms: float = 64.0,
                                 pattern: str = "0101",
                                 subarray: int = 0, mesh=None) -> np.ndarray:
    """(D, 9, 64) per-access error probability per burst-bit position — the
    population-scale Fig 12 profile feeding ``shuffling_gain_population``.

    Bit j of chip c reads mat ``burst_bit_to_mat(j)`` at the bit's column
    stride (the layout of ``DimmModel.burst_bit_error_counts``); its per-access
    error probability is the row-average failure probability at that (mat,
    col), from the same Pallas fail_prob grids as the profiling sweep.  The
    ECC chip (row 8) shares the die design but has no per-chip offset in the
    model, so it gets the across-data-chip mean profile.
    """
    from repro.core.geometry import burst_bit_to_mat
    g = batch.geom
    bits = np.arange(g.burst_bits)
    mats = burst_bit_to_mat(g, bits)
    within = bits % g.bits_per_mat_in_burst
    cols = (within * (g.cols_per_mat // g.bits_per_mat_in_burst)
            + g.cols_per_mat // (2 * g.bits_per_mat_in_burst))
    out = np.zeros((batch.n_dimms, 9, g.burst_bits), np.float32)
    for chip in range(g.chips):
        grids = fail_prob_grids(batch, param, t_op, temp_C=temp_C,
                                refresh_ms=refresh_ms, pattern=pattern,
                                chip=chip, subarray=subarray, mesh=mesh)
        # reduce on device: only (D, 64) floats cross to host per chip
        out[:, chip, :] = np.asarray(jnp.mean(grids, axis=2)[:, mats, cols])
    out[:, 8, :] = out[:, :g.chips, :].mean(axis=1)
    return out
