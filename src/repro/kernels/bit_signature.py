"""Pallas TPU kernel: per-address-bit error signatures (masked row-reduction).

The blind-discovery subsystem (Sec 5.3, Figs 10-11) characterizes a scrambled
error-count vector by, for every address bit b, the difference between the
total error count of rows with bit b SET and rows with it CLEAR.  That is a
bank of ``nbits`` masked reductions over the row axis; one program owns a
(TILE_N, R) slab of count vectors in VMEM, materializes each bit's ±1 mask
from an iota (no mask tensor ever leaves the kernel), and writes the
(TILE_N, nbits) int32 signature sums.  ``nbits = log2(R)`` is static, so the
per-bit loop unrolls at trace time.

Everything is int32: the reduction is exact and summation-order independent,
which is what lets the NumPy reference (``core/mapping._signature_sums``),
the jnp oracle (``kernels/ref.py::bit_signature``) and this kernel agree
value-for-value — the foundation of the recovery path's bit-parity story.
Counts must stay below ~2^31 / R per row for the int32 accumulator; the
simulated campaigns sit orders of magnitude under that.

The call is vmap-able over leading axes the same way ``fail_prob`` is; the
batched entry point (``discovery.signatures`` via ``kernels/ops.py``) instead
flattens (D, subarrays) into the row axis, which keeps one grid.

Registry contract: dispatched as ``bit_signature`` with tile space {default,
64, 128, 512} over the leading (vector) axis; padded all-zero count vectors
produce all-zero signatures and are sliced back, and the exact int32
reduction makes outputs bit-identical at any tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 256


def _make_kernel(nbits: int, n_rows: int, tile: int):
    def kernel(c_ref, o_ref):
        c = c_ref[...]                                    # (tile, R) i32
        r = jax.lax.broadcasted_iota(jnp.int32, (tile, n_rows), 1)
        cols = []
        for b in range(nbits):                            # static unroll
            pm = ((r >> b) & 1) * 2 - 1                   # ±1 mask for bit b
            cols.append(jnp.sum(c * pm, axis=1))
        o_ref[...] = jnp.stack(cols, axis=1)

    return kernel


@functools.partial(jax.jit, static_argnames=("nbits", "interpret", "tile"))
def bit_signature(counts, *, nbits: int, interpret: bool,
                  tile: int = TILE_N):
    """counts: (N, R) int32 per-row error counts (R = 2**nbits rows each).
    Returns (N, nbits) int32: per address bit, sum(rows with bit set) -
    sum(rows with bit clear)."""
    counts = jnp.asarray(counts, jnp.int32)
    n, R = counts.shape
    assert R == 2 ** nbits, (R, nbits)
    tile = min(tile, max(n, 1))
    pad = (-n) % tile
    if pad:
        counts = jnp.pad(counts, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _make_kernel(nbits, R, tile),
        grid=(counts.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, R), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, nbits), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((counts.shape[0], nbits), jnp.int32),
        interpret=interpret,
    )(counts)
    return out[:n]
