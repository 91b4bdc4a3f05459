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
so the kernel writes (rows,) floats and no grid reaches HBM.

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

from repro.core.latency import fail_mixture, retention_fail_mixture

N_COEFFS = 9  # base_eff, k_bl', k_wl', k_mat', k_row', t_op, sigma, rate, ns
# operating-point row: N_COEFFS access coefficients plus the voltage shift
# and the retention channel (ret_base, ret_k, ret_x, ret_sigma, ret_drop)
N_OP_COEFFS = 15
# the row-sum mode's row block (eight f32 sublane tiles) and column strip
_ROW_BLOCK = 64
_LANES = 128


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
    if open_bitline:
        d_bl = jnp.where(even, rf, (n_rows - 1.0) - rf) / (n_rows - 1.0)
    else:
        d_bl = rf / (n_rows - 1.0)
    d_wl = colf / (n_cols - 1.0)
    d_row = rf / (n_rows - 1.0)
    t = cf[0] + cf[1] * d_bl + cf[2] * d_wl + cf[3] * d_mat + cf[4] * d_row
    if voltage:
        t = t + cf[9]
    p = fail_mixture(t, cf[5], cf[6], cf[7], cf[8], xp=jnp)
    if retention:
        slow = cf[1] * d_bl + cf[2] * d_wl + cf[3] * d_mat + cf[4] * d_row
        p = p + retention_fail_mixture(slow, cf[10], cf[11], cf[12], cf[13],
                                       cf[7], cf[14], xp=jnp)
    return p


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


def _row_sum_call(row_src, d_mat, coeffs, cols: int, open_bitline: bool,
                  voltage: bool, retention: bool, row_tile: int | None,
                  interpret: bool):
    """The (R,) sum over mats and columns of ``cell_probs``, with no grid.

    One program owns one row tile and walks it in blocks of ``_ROW_BLOCK``
    rows; within a block, column strips of up to ``_LANES`` lanes; within a
    strip, the mats, read as SMEM scalars.  The row source and the column
    iota become full (block, strip) arrays once a row block and once a
    strip, so every operation of ``cell_probs`` works on whole vregs.  The
    mat loop is unrolled: its cells are independent chains the scheduler
    interleaves, and what does not depend on the mat is the same value in
    each.  Probabilities accumulate in a (block, strip) array that never
    leaves the core; its lanes are summed once at the block's end.  HBM
    traffic is the row-source read and R floats written."""
    row_src = jnp.asarray(row_src, jnp.int32).reshape(-1, 1)
    d_mat = jnp.asarray(d_mat, jnp.float32).reshape(1, -1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(1, -1)
    n = _n_coeffs(voltage, retention)
    R = row_src.shape[0]
    tile = -(-(row_tile or R) // _ROW_BLOCK) * _ROW_BLOCK
    row_src, R, tile = _row_grid(row_src, tile)
    Rp, M = row_src.shape[0], d_mat.shape[1]
    strip = _LANES if cols % _LANES == 0 else cols
    f32 = jnp.float32

    def kernel(rs_ref, dm_ref, cf_ref, out_ref):
        cf = [cf_ref[0, k] for k in range(n)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (_ROW_BLOCK, strip), 1)

        def row_block(b, carry):
            r0 = pl.multiple_of(b * _ROW_BLOCK, _ROW_BLOCK)
            rf = jnp.broadcast_to(
                rs_ref[pl.ds(r0, _ROW_BLOCK), :].astype(f32),
                (_ROW_BLOCK, strip))

            def col_strip(s, acc):
                col = lane + s * strip
                colf, even = col.astype(f32), (col % 2) == 0

                def mat(m, acc):
                    return acc + cell_probs(rf, colf, even, dm_ref[0, m], cf,
                                            R, cols, open_bitline, voltage,
                                            retention)

                return jax.lax.fori_loop(0, M, mat, acc, unroll=True)

            acc = jax.lax.fori_loop(0, cols // strip, col_strip,
                                    jnp.zeros((_ROW_BLOCK, strip), f32))
            out_ref[pl.ds(r0, _ROW_BLOCK), :] = acc.sum(axis=1, keepdims=True)
            return carry

        jax.lax.fori_loop(0, tile // _ROW_BLOCK, row_block, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // tile,),
        in_specs=[pl.BlockSpec((tile, 1), lambda j: (j, 0)), smem, smem],
        out_specs=pl.BlockSpec((tile, 1), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), f32),
        interpret=interpret,
    )(row_src, d_mat, coeffs)
    return out[:R, 0]


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
