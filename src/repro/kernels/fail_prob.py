"""Pallas TPU kernel: per-cell failure-probability grid (the DIVA model eval).

One program owns one mat's (rows, cols) slab and evaluates the whole latency
model in VMEM: distance-derived t_req (bitline / wordline / mat-position /
row-index terms, Figs 3-4/9), the operating-condition and chip/subarray
offsets (folded into the coefficient row), the heavy-tail weak-cell mixture
(Sec 6.1/App C), and the post-manufacturing row repair (resolved upstream
into the ``row_src`` index table).  At a full operating point two static
flags add the supply-voltage shift and the retention error channel, read
from a wider coefficient row.  HBM traffic is one read of the row-source
and coefficient rows and one write of the (mats, rows, cols) grid.

Callers that only sum the grid over mats and columns (the campaign's
expected row counts) ask for ``reduce_rows=True`` instead: the same
``cell_probs`` evaluated on register-sized blocks and accumulated in place,
so the kernel writes (rows,) floats and no grid reaches HBM.  That mode
skips work whose result is known exactly.  ``fail_probability`` is exactly
0.0 in float32 for every z <= -5.543 and exactly 1.0 for every z >= 5.347,
so a failure term whose z lies beyond +-``Z_SATURATED`` (6.5, about one
sigma to spare) over a whole (row block, column strip) tile, all its mats
included, is a constant there.  ``saturation_modes`` bounds t over each tile from scalars
alone and gives it one of five modes; the kernel adds, per mode, the same
float32 value the full mixture gives (a term known to be 0 contributes an
exact +0, one known to be 1 an exact ``rate``), into the same accumulator
in the same mat order, so the row sums keep every bit.

The call is vmap-able over DIMMs / chips / subarrays / patterns — the
batching rule adds grid dimensions — which is how core/substrate.py profiles
the whole population.  Semantics match ``kernels/ref.py::fail_prob`` to one
float32 ulp (same jnp ops; XLA fuses the two programs differently) and
``DimmModel.fail_prob_grid`` to float32 rounding of the folded coefficients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.latency import (fail_mixture, fail_probability,
                                retention_fail_mixture)

N_COEFFS = 9  # base_eff, k_bl', k_wl', k_mat', k_row', t_op, sigma, rate, ns
# operating-point row: N_COEFFS access coefficients plus the voltage shift
# and the retention channel (ret_base, ret_k, ret_x, ret_sigma, ret_drop)
N_OP_COEFFS = 15
# the row-sum mode's row block (eight f32 sublane tiles) and column strip
_ROW_BLOCK = 64
_LANES = 128
# |z| from which ``fail_probability`` is exactly 0 or 1 in float32 (it is
# from z <= -5.543 and from z >= 5.347)
Z_SATURATED = 6.5
# what the row-sum mode evaluates of a tile's two failure terms, the main
# term p and the outlier term p_out of ``fail_mixture``: both, p_out alone
# (p is 0), p alone (p_out is 1), neither (both 0: nothing is added) or
# neither (both 1: a constant is added)
EVAL_BOTH, P_ZERO, OUT_ONE, BOTH_ZERO, BOTH_ONE = range(5)
# terms each mode evaluates, of the two
MODE_EVALS = (2, 1, 1, 0, 0)


def cell_probs(rf, colf, even, d_mat, cf, n_rows: int, n_cols: int,
               open_bitline: bool = True, voltage: bool = False,
               retention: bool = False):
    """Failure probability of each cell; shared by the kernel and the oracle.

    ``rf``/``colf``/``even`` broadcast to the (rows, cols) slab; ``cf`` is the
    folded coefficient row (stress pre-multiplied into the k's, all additive
    offsets folded into cf[0]).  Static ``voltage`` shifts the access channel
    by the folded supply term cf[9]; static ``retention`` adds the
    refresh/temperature retention channel, whose slowness is the same
    stress-premultiplied design-variation sum the access channel uses
    (``t - cf[0]``).  Channel probabilities ADD (expected-count channels), so
    summing the returned grid over cells yields the two-channel lambda.  With
    both flags off only cf[:N_COEFFS] is read.
    """
    return _mode_probs(EVAL_BOTH, _mat_free(rf, colf, even, cf, n_rows,
                                            n_cols, open_bitline),
                       d_mat, cf, voltage, retention)


def _mat_free(rf, colf, even, cf, n_rows: int, n_cols: int,
              open_bitline: bool):
    """What a cell's terms share across mats: its bitline, wordline and
    row-index distances, ``cf[0] + cf[1]*d_bl + cf[2]*d_wl`` and
    ``cf[4]*d_row``."""
    if open_bitline:
        d_bl = jnp.where(even, rf, (n_rows - 1.0) - rf) / (n_rows - 1.0)
    else:
        d_bl = rf / (n_rows - 1.0)
    d_wl = colf / (n_cols - 1.0)
    d_row = rf / (n_rows - 1.0)
    return (d_bl, d_wl, d_row, cf[0] + cf[1] * d_bl + cf[2] * d_wl,
            cf[4] * d_row)


def _mode_probs(mode: int, parts, d_mat, cf, voltage: bool,
                retention: bool):
    """``cell_probs`` from its ``_mat_free`` parts, with the access
    channel's terms as ``mode`` says: evaluated, or replaced by the exact
    0 or 1 they evaluate to inside the mixture's own expression, so the
    value is the full formula's, bit for bit.  None where nothing is added
    (both terms 0, no retention channel)."""
    d_bl, d_wl, d_row, pre, row_term = parts
    rate = cf[7]
    p = None
    if mode == BOTH_ONE:
        p = (1.0 - rate) + rate
    elif mode != BOTH_ZERO:
        t = pre + cf[3] * d_mat + row_term
        if voltage:
            t = t + cf[9]
        if mode == EVAL_BOTH:
            p = fail_mixture(t, cf[5], cf[6], rate, cf[8], xp=jnp)
        else:
            main = (jnp.zeros_like(t) if mode == P_ZERO
                    else fail_probability(t, cf[5], cf[6], xp=jnp))
            out = (jnp.ones_like(t) if mode == OUT_ONE
                   else fail_probability(t + cf[8], cf[5], cf[6], xp=jnp))
            p = (1.0 - rate) * main + rate * out
    if retention:
        slow = cf[1] * d_bl + cf[2] * d_wl + cf[3] * d_mat + row_term
        r = retention_fail_mixture(slow, cf[10], cf[11], cf[12], cf[13],
                                   rate, cf[14], xp=jnp)
        p = r if p is None else p + r
    return p


def saturation_modes(row_src, d_mat, cf, *, n_rows: int, cols: int,
                     open_bitline: bool, voltage: bool):
    """The (blocks, strips) int32 mode of each (row block, column strip)
    tile of the row-sum mode, one for all its mats: ``row_src`` (Rp,) is
    the row source padded to whole row blocks, ``n_rows`` the unpadded row
    count that normalizes the distances.

    t over a tile lies between the sums of each term's smaller and larger
    end: the block's smallest and largest source row (repaired rows make a
    block's rows any rows; under the open bitline both parities share every
    strip), the strip's first and last column, the smallest and largest mat
    delay, each scaled by its coefficient of either sign.  A term is a
    constant where z =
    (t - t_op) / max(sigma, 1e-6) lies beyond +-``Z_SATURATED`` over the
    whole span, widened by a few float32 ulps of the sum's magnitude so
    that the rounding of t in the kernel cannot cross it."""
    f32 = jnp.float32
    n = n_rows - 1.0
    rs = row_src.reshape(-1, _ROW_BLOCK).astype(f32)
    r_lo, r_hi = rs.min(axis=1), rs.max(axis=1)
    if open_bitline:
        bl = (jnp.minimum(r_lo, n - r_hi) / n, jnp.maximum(r_hi, n - r_lo) / n)
    else:
        bl = (r_lo / n, r_hi / n)
    strip = _strip(cols)
    c0 = jnp.arange(0, cols, strip, dtype=f32)
    wl = (c0 / (cols - 1.0), (c0 + (strip - 1)) / (cols - 1.0))
    d_mat = jnp.asarray(d_mat, f32)

    def span(k, ends, shape):
        a, b = (cf[k] * e.reshape(shape) for e in ends)
        return jnp.minimum(a, b), jnp.maximum(a, b)

    terms = (span(1, bl, (-1, 1)), span(2, wl, (1, -1)),
             span(3, (d_mat.min(), d_mat.max()), ()),
             span(4, (r_lo / n, r_hi / n), (-1, 1)))
    t_lo = cf[0] + sum(lo for lo, _ in terms)
    t_hi = cf[0] + sum(hi for _, hi in terms)
    magnitude = (jnp.abs(cf[0]) + jnp.abs(cf[1]) + jnp.abs(cf[2])
                 + jnp.abs(cf[3]) * jnp.abs(d_mat).max() + jnp.abs(cf[4])
                 + jnp.abs(cf[5]) + jnp.abs(cf[8]))
    if voltage:
        t_lo, t_hi = t_lo + cf[9], t_hi + cf[9]
        magnitude = magnitude + jnp.abs(cf[9])
    slack = magnitude * 2.0 ** -18
    sigma = jnp.maximum(cf[6], 1e-6)

    def known(shift):
        """(certainly 0, certainly 1) of the term at ``t + shift``."""
        return ((t_hi + shift + slack - cf[5]) / sigma <= -Z_SATURATED,
                (t_lo + shift - slack - cf[5]) / sigma >= Z_SATURATED)

    p0, p1 = known(0.0)
    o0, o1 = known(cf[8])
    return jnp.select([p0 & o0, p1 & o1, p0, o1],
                      [BOTH_ZERO, BOTH_ONE, P_ZERO, OUT_ONE],
                      EVAL_BOTH).astype(jnp.int32)


def _strip(cols: int) -> int:
    """The row-sum mode's column strip: whole vregs where the width allows."""
    return _LANES if cols % _LANES == 0 else cols


def _n_coeffs(voltage: bool, retention: bool) -> int:
    """Width of the coefficient row the kernel reads under these flags."""
    return N_OP_COEFFS if voltage or retention else N_COEFFS


def _grid_call(row_src, d_mat, coeffs, cols: int, open_bitline: bool,
               voltage: bool, retention: bool, row_tile: int | None,
               interpret: bool):
    """The (M, R, C) grid of ``cell_probs``.

    One program owns one (mat, row-tile) slab.  The per-mat precharge delay
    and the folded coefficient row are read as scalars from SMEM (Mosaic
    refuses a (1, 1) VMEM block over an (M, 1) array), so the row axis is the
    only blocked input and tiles in multiples of 8.  Distance normalization
    always uses the GLOBAL row count — rf comes from the row-source VALUES,
    not the block position, so the per-cell computation is independent of
    how the row axis is tiled (the tile-invariance contract)."""
    row_src = jnp.asarray(row_src, jnp.int32).reshape(-1, 1)
    # (1, n) rows: a vmapped SMEM operand gains a squeezed leading block
    # dim, and Mosaic accepts the (1, n) trailing block only as the whole
    # array's trailing dims
    d_mat = jnp.asarray(d_mat, jnp.float32).reshape(1, -1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(1, -1)
    n = _n_coeffs(voltage, retention)
    row_src, R, tile = _row_grid(row_src, row_tile)
    Rp, M = row_src.shape[0], d_mat.shape[1]

    def kernel(rs_ref, dm_ref, cf_ref, out_ref):
        rf = jnp.broadcast_to(rs_ref[...].astype(jnp.float32), (tile, cols))
        col = jax.lax.broadcasted_iota(jnp.int32, (tile, cols), 1)
        cf = [cf_ref[0, k] for k in range(n)]
        out_ref[...] = cell_probs(rf, col.astype(jnp.float32), (col % 2) == 0,
                                  dm_ref[0, pl.program_id(0)], cf, R, cols,
                                  open_bitline, voltage, retention)[None]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(M, Rp // tile),
        in_specs=[pl.BlockSpec((tile, 1), lambda i, j: (j, 0)), smem, smem],
        out_specs=pl.BlockSpec((1, tile, cols), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((M, Rp, cols), jnp.float32),
        interpret=interpret,
    )(row_src, d_mat, coeffs)
    return out[:, :R]


def _row_grid(row_src, row_tile: int | None):
    """Pad the (R, 1) row-source to the row tile; returns (padded, R, tile).
    ``row_tile=None`` keeps the whole-R single block (the untiled default)."""
    R = row_src.shape[0]
    if row_tile is None:
        return row_src, R, R
    pad = (-R) % row_tile
    if pad:  # padded rows index row 0: computed, then sliced off below
        row_src = jnp.pad(row_src, ((0, pad), (0, 0)))
    return row_src, R, row_tile


def _row_sum_layout(row_src, d_mat, coeffs, cols: int, open_bitline: bool,
                    voltage: bool, row_tile: int | None):
    """The row-sum mode's operands: the (Rp, 1) row source padded to whole
    tiles, the unpadded row count, the tile, and the mode table as a
    (1, blocks * strips) row for SMEM."""
    row_src = jnp.asarray(row_src, jnp.int32).reshape(-1, 1)
    d_mat = jnp.asarray(d_mat, jnp.float32).reshape(1, -1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(1, -1)
    R = row_src.shape[0]
    tile = -(-(row_tile or R) // _ROW_BLOCK) * _ROW_BLOCK
    row_src, R, tile = _row_grid(row_src, tile)
    modes = saturation_modes(row_src[:, 0], d_mat[0], coeffs[0], n_rows=R,
                             cols=cols, open_bitline=open_bitline,
                             voltage=voltage)
    return row_src, d_mat, coeffs, R, tile, modes.reshape(1, -1)


def _row_sum_call(row_src, d_mat, coeffs, cols: int, open_bitline: bool,
                  voltage: bool, retention: bool, row_tile: int | None,
                  interpret: bool):
    """The (R,) sum over mats and columns of ``cell_probs``, with no grid.

    One program owns one row tile and walks it in blocks of ``_ROW_BLOCK``
    rows; within a block, column strips of up to ``_LANES`` lanes; within a
    strip, the mats, read as SMEM scalars.  The row source and the column
    iota become full (block, strip) arrays once a row block and once a
    strip, and so do the parts of t that do not depend on the mat, so every
    operation works on whole vregs.

    Each (block, strip) tile switches on its mode from
    ``saturation_modes``, read as an int32 SMEM scalar, into one of five
    bodies, each an unrolled loop over the mats whose cells are
    independent chains the scheduler interleaves: both failure terms
    evaluated as ``fail_mixture`` does; the outlier term alone, ``(1 -
    rate) * 0 + rate * p_out``, where p is 0; the main term alone, ``(1 -
    rate) * p + rate * 1``, where p_out is 1; nothing where both are 0 (+0
    is the identity of the sum); the constant ``(1 - rate) + rate`` where
    both are 1.  Each adds the value the full mixture gives, bit for bit,
    mat after mat as before.  One decision for all the mats keeps the
    loops branch-free: a decision per mat skips more terms but breaks
    the interleaving, and ran the campaign a third slower on a v5e.  The
    retention channel has no such bound and is evaluated in every mode.
    Probabilities
    accumulate in a (block, strip) array that never leaves the core; its
    lanes are summed once at the block's end.  HBM traffic is the
    row-source read and R floats written."""
    row_src, d_mat, coeffs, R, tile, modes = _row_sum_layout(
        row_src, d_mat, coeffs, cols, open_bitline, voltage, row_tile)
    n = _n_coeffs(voltage, retention)
    Rp, M = row_src.shape[0], d_mat.shape[1]
    strip = _strip(cols)
    n_strips, blocks = cols // strip, tile // _ROW_BLOCK
    f32 = jnp.float32

    def kernel(rs_ref, dm_ref, cf_ref, md_ref, out_ref):
        cf = [cf_ref[0, k] for k in range(n)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (_ROW_BLOCK, strip), 1)
        block0 = pl.program_id(0) * blocks

        def row_block(b, carry):
            r0 = pl.multiple_of(b * _ROW_BLOCK, _ROW_BLOCK)
            rf = jnp.broadcast_to(
                rs_ref[pl.ds(r0, _ROW_BLOCK), :].astype(f32),
                (_ROW_BLOCK, strip))

            def col_strip(s, acc):
                col = lane + s * strip
                parts = _mat_free(rf, col.astype(f32), (col % 2) == 0, cf, R,
                                  cols, open_bitline)

                def strip_add(mode):
                    def add(acc):
                        def mat(m, acc):
                            p = _mode_probs(mode, parts, dm_ref[0, m], cf,
                                            voltage, retention)
                            return acc if p is None else acc + p
                        return jax.lax.fori_loop(0, M, mat, acc, unroll=True)
                    return add

                return jax.lax.switch(
                    md_ref[0, (block0 + b) * n_strips + s],
                    [strip_add(k) for k in range(len(MODE_EVALS))], acc)

            acc = jax.lax.fori_loop(0, n_strips, col_strip,
                                    jnp.zeros((_ROW_BLOCK, strip), f32))
            out_ref[pl.ds(r0, _ROW_BLOCK), :] = acc.sum(axis=1, keepdims=True)
            return carry

        jax.lax.fori_loop(0, blocks, row_block, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // tile,),
        in_specs=[pl.BlockSpec((tile, 1), lambda j: (j, 0)), smem, smem,
                  smem],
        out_specs=pl.BlockSpec((tile, 1), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), f32),
        interpret=interpret,
    )(row_src, d_mat, coeffs, modes)
    return out[:R, 0]


def erf_evals(row_src, d_mat, coeffs, *, cols: int, open_bitline: bool = True,
              voltage: bool = False, row_tile: int | None = None):
    """int32 (evaluated, saturated): the access channel's failure terms the
    row-sum mode evaluates on these arguments, and those it skips as exactly
    0 or 1, counted in (row block, column strip) tiles of one mat and one
    term, summed from the mode table the kernel reads.  Evaluated plus
    saturated is twice the tiles times the mats."""
    _, d_mat, _, _, _, modes = _row_sum_layout(
        row_src, d_mat, coeffs, cols, open_bitline, voltage, row_tile)
    M = d_mat.shape[1]
    evaluated = jnp.asarray(MODE_EVALS, jnp.int32)[modes].sum() * M
    return jnp.stack([evaluated, 2 * modes.size * M - evaluated])


@functools.partial(jax.jit, static_argnames=("cols", "open_bitline",
                                             "voltage", "retention",
                                             "row_tile", "reduce_rows",
                                             "interpret"))
def fail_prob(row_src, d_mat, coeffs, *, cols: int, interpret: bool,
              open_bitline: bool = True, voltage: bool = False,
              retention: bool = False, row_tile: int | None = None,
              reduce_rows: bool = False):
    """row_src: (R,) int32 repair-resolved internal rows; d_mat: (M,) f32
    precharge-arrival delays; coeffs: the f32 folded coefficient row, whose
    first N_COEFFS entries are the access channel's and, where ``voltage``
    or ``retention`` is set, N_OP_COEFFS wide: ``[*access 0-8, vdd_shift,
    ret_base, ret_k, ret_x, ret_sigma, ret_drop]``.  Returns the (M, R, C)
    failure-probability grid, or with ``reduce_rows`` its (R,) sum over mats
    and columns, computed without writing the grid.

    ``row_tile`` splits the row axis into a second grid dimension (masked
    tail via pad-to-tile + slice-back); per-cell results are bit-identical at
    any tile because each row's computation is independent.  In the row-sum
    mode it is the rows one program owns, rounded up to whole row blocks."""
    call = _row_sum_call if reduce_rows else _grid_call
    return call(row_src, d_mat, coeffs, cols, open_bitline, voltage,
                retention, row_tile, interpret)
