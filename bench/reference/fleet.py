"""Plain reference of a served DIVA timing table.

A DIMM's table is what DIVA Profiling (Lee et al., SIGMETRICS'17, Sec 6.1)
finds for it: for tRCD, tRAS, tRP and tWR in turn, walk the parameter down
its 2.5 ns test grid from the standard value, test the row region at each
point, stop at the first point that fails (or that undercuts the floor:
5 ns, and tRCD + 10 ns for tRAS), and serve the last passing point plus one
cycle of guard band, capped at the standard value.

A test of the region at ``t_op`` writes four row-stripe patterns and their
inverses ``ITERS`` times into every cell of the region, in every mat of
every subarray and chip.  A cell needs ``t_req`` ns, the model's sum of
design-induced distance terms (bitline, wordline, mat position, row
index), the operating-condition adder and the chip and subarray offsets; it
fails at ``t_op`` with probability ``Phi((t_req - t_op) / sigma)``, mixed
with a weak-cell tail.  Under ECC the test counts only codewords with two
or more failing bits.  Each (subarray, pattern) pair of the test fails when
its counter-hash uniform lies below ``P(N > 0) = 1 - exp(-lambda)``, with
``lambda`` the expected number of failing codewords: the draw is keyed by
(serial, parameter, quarter-ns t_op, ECC criterion, subarray, pattern), so
any two implementations of the model make the same draws.

This module imports nothing of the program under test.  It takes a DIMM's
parameters as plain arrays (the inputs the benchmark generates), evaluates
every test point of every DIMM in plain ``jax.numpy`` on the device, one
block of DIMMs at a time, and walks the grids on the host.  ``dtype``
selects the precision of the per-cell arithmetic: float32 is the reference,
bfloat16 its control (see ``bench/tests/test_controls.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PARAMS = ("trcd", "tras", "trp", "twr")
STANDARD_NS = (13.75, 35.0, 13.75, 15.0)
CYCLE_NS = 1.25           # DDR3-1600 clock period: the guard band's unit
STEP_NS = 2.5             # the test infrastructure's timing step
FLOOR_NS = 5.0            # the lowest timing the infrastructure can set
TRAS_OVER_TRCD_NS = 10.0  # tRAS is never tested below tRCD + 10 ns
PATTERN_STRESS = (0.90, 1.00, 0.96, 0.94)   # 0000, 0101, 0011, 1001
ITERS = 10
CODEWORD_BITS = 72
#: leaves of a DIMM that the reference reads
LEAVES = ("serial", "base", "k_bl", "k_wl", "k_mat", "k_row", "sigma",
          "outlier_rate", "outlier_ns", "temp_coef", "refresh_coef",
          "aging_coef", "chip_offsets", "sub_offsets", "row_src")


def timing_grid(standard: float) -> np.ndarray:
    """Test points of one parameter, from the largest grid point at or
    below ``standard`` down to the floor."""
    top = np.floor(standard / STEP_NS) * STEP_NS
    n = int(np.floor((top - FLOOR_NS) / STEP_NS + 1e-9)) + 1
    return np.round(top - STEP_NS * np.arange(n), 3)


def design_worst_rows(rows_per_mat: int) -> np.ndarray:
    """Internal rows of the latency test region: a mat's two edge rows,
    which hold the cells farthest from their sense amplifiers on both
    halves of the open bitlines (paper Sec 6.1)."""
    return np.asarray([0, rows_per_mat - 1])


def _tests():
    """(parameter index, t_op) of every test point, parameters in order."""
    pidx, t_op = [], []
    for i, std in enumerate(STANDARD_NS):
        g = timing_grid(std)
        pidx += [i] * len(g)
        t_op += list(g)
    return np.asarray(pidx, np.int32), np.asarray(t_op, np.float32)


def _mix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def test_uniform(serial, pidx, t_q, multibit, sub, pat):
    """The uniform in [0, 1) that decides one (subarray, pattern) test."""
    u32 = lambda v: jnp.asarray(v, jnp.uint32)
    h = u32(serial) * jnp.uint32(0x9E3779B9)
    h = _mix32(h ^ (u32(pidx) * jnp.uint32(0x85EBCA6B)))
    h = _mix32(h ^ (u32(t_q) * jnp.uint32(0xC2B2AE35)))
    h = _mix32(h ^ (u32(multibit) + u32(sub) * jnp.uint32(0x27D4EB2F)
                    + u32(pat) * jnp.uint32(0x165667B1)))
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _phi(z):
    """Normal CDF through the Abramowitz-Stegun 7.1.26 erf."""
    x = z / np.sqrt(2.0).astype(z.dtype)
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * a)
    y = 1.0 - (((((1.061405429 * t - 1.453152027) * t) + 1.421413741) * t
                - 0.284496736) * t + 0.254829592) * t * jnp.exp(-a * a)
    return 0.5 * (1.0 + jnp.sign(x) * y)


def _cell_fail(t_req, t_op, sigma, rate, extra):
    """P(cell fails at t_op), with the weak-cell tail: a fraction ``rate``
    of cells needs ``extra`` ns more."""
    s = jnp.maximum(sigma, 1e-6)
    return (1.0 - rate) * _phi((t_req - t_op) / s) \
        + rate * _phi((t_req + extra - t_op) / s)


def _two_or_more(q):
    """P(at least two of a codeword's 72 bits fail | per-bit probability
    q), in the expm1/log1p form that stays accurate for small q."""
    q = jnp.clip(q, 0.0, 0.999999)
    l1 = jnp.log1p(-q)
    return jnp.clip(-jnp.expm1(CODEWORD_BITS * l1)
                    - CODEWORD_BITS * q * jnp.exp((CODEWORD_BITS - 1) * l1),
                    0.0, 1.0)


def _mat_delay(mats: int) -> np.ndarray:
    """Normalised precharge-signal arrival per mat (paper Fig 9): the
    earlier of the main signal (left to right) and the sub signal (two mat
    delays late, right to left)."""
    m = np.arange(mats, dtype=np.float32)
    d = np.minimum(m + 1.0, 2.0 + (mats - 1.0 - m)).astype(np.float32)
    return (d / d.max()).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("cols", "mats", "chips",
                                             "multibit", "dtype"))
def _test_fails(lv, rows, adder, pidx, t_op, *, cols, mats, chips,
                multibit, dtype):
    """(tests, B) bool: does each DIMM of the block fail each test point."""
    R = lv["row_src"].shape[2]
    S = lv["row_src"].shape[1]
    f = lambda a: jnp.asarray(a, dtype)
    c = jnp.arange(cols)
    even = (c % 2) == 0
    d_wl = f(c.astype(jnp.float32) / (cols - 1))
    d_mat = f(_mat_delay(mats))
    stress = f(np.asarray(PATTERN_STRESS, np.float32))
    t_q = jnp.round(t_op * 4).astype(jnp.uint32)

    def one_test(args):
        p, top, tq = args
        coef = lambda name: f(jnp.take(lv[name], p, axis=1))  # (B,)
        base, kbl, kwl = coef("base"), coef("k_bl"), coef("k_wl")
        kmat, krow = coef("k_mat"), coef("k_row")

        def one_sub(s):
            rf = jnp.take(lv["row_src"][:, s, :], rows, axis=1) \
                .astype(jnp.float32)                            # (B, Rr)
            d_bl = f(jnp.where(even[None, None, :], rf[:, :, None],
                               (R - 1) - rf[:, :, None]) / (R - 1))
            d_row = f(rf / (R - 1))
            var = (kbl[:, None, None, None] * d_bl[:, None, :, :]
                   + kwl[:, None, None, None] * d_wl[None, None, None, :]
                   + kmat[:, None, None, None] * d_mat[None, :, None, None]
                   + krow[:, None, None, None] * d_row[:, None, :, None])
            t = base[:, None, None, None, None] \
                + stress[None, :, None, None, None] * var[:, None]
            t = t + f(adder)[:, None, None, None, None]
            t = t + f(lv["chip_offsets"][:, 0])[:, None, None, None, None]
            t = t + f(lv["sub_offsets"][:, s])[:, None, None, None, None]
            q = _cell_fail(t, f(top), f(lv["sigma"])[:, None, None, None, None],
                           f(lv["outlier_rate"])[:, None, None, None, None],
                           f(lv["outlier_ns"])[:, None, None, None, None])
            if multibit:
                lam = 2 * ITERS * chips * jnp.sum(
                    _two_or_more(q), axis=(2, 3, 4), dtype=jnp.float32) \
                    / float(CODEWORD_BITS)
                lam = jnp.maximum(lam, 0.0)
            else:
                lam = 2 * ITERS * chips * jnp.sum(q, axis=(2, 3, 4),
                                                  dtype=jnp.float32)
            u = test_uniform(lv["serial"][:, None], p, tq, int(multibit), s,
                             jnp.arange(len(PATTERN_STRESS))[None, :])
            return jnp.any(u < -jnp.expm1(-lam), axis=1)         # (B,)

        return jnp.any(jax.lax.map(one_sub, jnp.arange(S)), axis=0)

    return jax.lax.map(one_test, (pidx, t_op, t_q))


def condition_adder(lv: dict, age_years: float, temp_C: float,
                    refresh_ms: float) -> np.ndarray:
    """(D,) f32 operating-condition term: temperature off the 85 C anchor,
    refresh-interval doublings, and aging drift."""
    t_delta = np.float32(temp_C - 85.0)
    r_log = np.float32(np.log2(max(refresh_ms, 1.0) / 64.0))
    return (np.asarray(lv["temp_coef"], np.float32) * t_delta
            + np.asarray(lv["refresh_coef"], np.float32) * r_log
            + np.asarray(lv["aging_coef"], np.float32)
            * np.float32(age_years))


def walk(fails: np.ndarray, guard_cycles: int) -> np.ndarray:
    """(D, 4) tables from the (tests, D) pass/fail matrix."""
    D = fails.shape[1]
    out = np.zeros((D, len(PARAMS)), np.float64)
    row = 0
    for i, std in enumerate(STANDARD_NS):
        grid = timing_grid(std)
        f = fails[row:row + len(grid)]
        row += len(grid)
        floor = out[:, 0] + TRAS_OVER_TRCD_NS if i == 1 \
            else np.full(D, FLOOR_NS)
        stop = f | (grid[:, None] < floor[None, :] - 1e-9)
        ok = np.cumsum(stop, axis=0) == 0
        best = np.where(ok, grid[:, None], np.inf).min(axis=0)
        best = np.where(np.isfinite(best), best, std)
        out[:, i] = np.minimum(best + guard_cycles * CYCLE_NS, std)
    return out.astype(np.float32)


def tables(lv: dict, rows, *, age_years: float, temp_C: float,
           refresh_ms: float, guard_cycles: int, multibit: bool,
           cols: int, mats: int, chips: int, block: int,
           dtype=jnp.float32) -> np.ndarray:
    """(D, 4) float32 tables of the DIMMs in ``lv`` (a dict of (D, ...)
    arrays, ``LEAVES``) tested over the internal ``rows`` of every mat,
    ``block`` DIMMs per device call (the last block is padded by repeating
    its last DIMM)."""
    D = len(lv["serial"])
    adder = condition_adder(lv, age_years, temp_C, refresh_ms)
    pidx, t_op = _tests()
    rows = jnp.asarray(np.asarray(rows, np.int32))
    fails = []
    for lo in range(0, D, block):
        idx = np.minimum(np.arange(lo, lo + block), D - 1)
        blk = {k: jnp.asarray(np.asarray(lv[k])[idx]) for k in LEAVES}
        out = _test_fails(blk, rows, jnp.asarray(adder[idx]),
                          jnp.asarray(pidx), jnp.asarray(t_op), cols=cols,
                          mats=mats, chips=chips, multibit=multibit,
                          dtype=dtype)
        fails.append(np.asarray(out)[:, :min(block, D - lo)])
    return walk(np.concatenate(fails, axis=1), guard_cycles)
