"""Pallas TPU kernel: DIVA Shuffling as a permutation matmul.

A burst is 9 chips x 64 bits = 576 bit lanes; DIVA Shuffling is a fixed
permutation of those lanes (chip i's beat rotated by i). Dynamic gathers are
awkward on the TPU vector unit, so the kernel applies the permutation as a
(TILE_N, 576) @ (576, 576) 0/1 matmul — the MXU eats it, and the permutation
matrix is built once from core/shuffling.beat_of_bit. The inverse permutation
(deshuffle) is the transpose.

``apply_shuffle(shuffle=False)`` applies the UNSHUFFLED burst layout (every
chip's bit b lands in beat b // 8) — the Fig 16a baseline the Fig 17
experiment compares against — and ``perm=`` accepts any custom 576-lane
permutation (memsys/codec.py uses its round-robin interleave here), so every
lane-permutation in the repo runs through this one kernel.

Registry contract: dispatched as ``diva_shuffle`` with tile space {default,
64, 128, 512} over the burst axis; bursts pad to the tile (zero bursts
permute to zero, sliced back), and a 0/1 permutation matmul is exact int
arithmetic in f32, so outputs are bit-identical at any tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.shuffling import N_DQ, beat_of_bit

LANES = 9 * 64
TILE_N = 256


@functools.lru_cache(maxsize=None)
def shuffle_permutation(shuffle: bool = True) -> np.ndarray:
    """perm[i] = source lane for output lane i (output = burst laid out as
    (beat, chip, dq); chip beats rotated when ``shuffle``, identity layout —
    beat = bit // 8 for every chip — when not). Cached; treat as read-only."""
    perm = np.zeros(LANES, np.int32)
    for chip in range(9):
        for bit in range(64):
            beat = int(beat_of_bit(bit, chip, shuffle and chip < 8))
            dq = bit % N_DQ
            out_lane = beat * 72 + chip * N_DQ + dq
            perm[out_lane] = chip * 64 + bit
    return perm


def permutation_matrix(perm: np.ndarray) -> np.ndarray:
    m = np.zeros((LANES, LANES), np.float32)
    m[perm, np.arange(LANES)] = 1.0
    return m


def _permute_kernel(x_ref, p_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (TILE_N, 576)
    p = p_ref[...]                               # (576, 576)
    o_ref[...] = jnp.dot(x, p, preferred_element_type=jnp.float32).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _permute(x, pm, *, interpret: bool, tile: int):
    n = x.shape[0]
    pad = (-n) % tile
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _permute_kernel,
        grid=(x.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((LANES, LANES), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], LANES), jnp.int32),
        interpret=interpret,
    )(x, pm)
    return out[:n]


@functools.lru_cache(maxsize=None)
def _perm_matrix(perm_bytes: bytes, inverse: bool) -> np.ndarray:
    """Host-side permutation matrix, built once per distinct (permutation,
    direction). Kept numpy (jnp constants created under a jit trace must not
    be cached — they would leak tracers)."""
    pm = permutation_matrix(np.frombuffer(perm_bytes, np.int32))
    return pm.T if inverse else pm


def apply_shuffle(bursts, *, interpret: bool, inverse: bool = False,
                  shuffle: bool = True, perm: np.ndarray | None = None,
                  tile: int = TILE_N):
    """bursts: (N, 576) 0/1 int32 lanes -> permuted (or un-permuted) lanes.

    ``perm`` overrides the permutation (default: ``shuffle_permutation``,
    honouring ``shuffle``); the permutation matrix is cached host-side per
    distinct permutation, so repeated calls skip the 576x576 rebuild.
    """
    if perm is None:
        perm = shuffle_permutation(shuffle)
    pm = _perm_matrix(np.asarray(perm, np.int32).tobytes(), inverse)
    return _permute(jnp.asarray(bursts, jnp.int32), jnp.asarray(pm),
                    interpret=interpret, tile=tile)
