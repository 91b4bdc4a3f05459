"""Pallas TPU kernel: SECDED(72,64) encode + syndrome, tiled over codewords.

The mod-2 parity computation is a (TILE_N, 64) @ (64, 8) matmul with exact
small-integer arithmetic in fp32 (values <= 72 are exactly representable), so
the MXU does the parity trees. Checkpoint scrubbing runs this over GBs of
data — the paper's controller-side ECC path is exactly this compute shape.

VMEM: in tile (TILE_N, 64) f32 = 128 KiB at TILE_N=512, H (64,8) resident,
out (TILE_N, 8) — comfortably under the ~16 MiB VMEM budget; TILE_N is the
only tuning knob and is MXU-aligned (multiples of 8/128 for f32 sublanes).

Registry contract (``kernels/registry.py``): dispatched as ``secded_encode``
/ ``secded_syndrome`` with tile space {default, 128, 256, 1024}; non-dividing
tiles take the masked-tail route (``_pad_to`` + slice-back: padded all-zero
codewords encode/syndrome to zero and are discarded), and because every
codeword's parity is independent the outputs are exact-int identical at ANY
tile — the template tile-invariance contract every integer kernel follows
(``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ecc import CHECK_BITS, DATA_BITS, H_DATA, H_FULL

TILE_N = 512


def _encode_kernel(x_ref, h_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (TILE_N, 64)
    h = h_ref[...].astype(jnp.float32)          # (64, 8)
    acc = jnp.dot(x, h, preferred_element_type=jnp.float32)
    o_ref[...] = (acc.astype(jnp.int32) % 2).astype(jnp.int32)


def _syndrome_kernel(c_ref, h_ref, o_ref):
    c = c_ref[...].astype(jnp.float32)          # (TILE_N, 72)
    h = h_ref[...].astype(jnp.float32)          # (72, 8)
    acc = jnp.dot(c, h, preferred_element_type=jnp.float32)
    o_ref[...] = (acc.astype(jnp.int32) % 2).astype(jnp.int32)


def _pad_to(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, n


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def encode_checks(data_bits, *, interpret: bool, tile: int = TILE_N):
    """(N, 64) 0/1 int32 -> (N, 8) check bits."""
    x, n = _pad_to(jnp.asarray(data_bits, jnp.int32), tile)
    grid = (x.shape[0] // tile,)
    out = pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile, DATA_BITS), lambda i: (i, 0)),
                  pl.BlockSpec((DATA_BITS, CHECK_BITS), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tile, CHECK_BITS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], CHECK_BITS), jnp.int32),
        interpret=interpret,
    )(x, jnp.asarray(H_DATA, jnp.int32))
    return out[:n]


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def syndrome(code_bits, *, interpret: bool, tile: int = TILE_N):
    """(N, 72) 0/1 int32 -> (N, 8) syndrome bits."""
    x, n = _pad_to(jnp.asarray(code_bits, jnp.int32), tile)
    grid = (x.shape[0] // tile,)
    out = pl.pallas_call(
        _syndrome_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile, DATA_BITS + CHECK_BITS), lambda i: (i, 0)),
                  pl.BlockSpec((DATA_BITS + CHECK_BITS, CHECK_BITS), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tile, CHECK_BITS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], CHECK_BITS), jnp.int32),
        interpret=interpret,
    )(x, jnp.asarray(H_FULL, jnp.int32))
    return out[:n]
