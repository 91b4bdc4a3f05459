"""Pallas TPU kernel: fused WKV6 recurrence (RWKV-6 time mixing).

Grid = (BH tiles [parallel], seq chunks [arbitrary/sequential]). The
(TILE_BH, dh, dh) state lives in a VMEM scratch that persists across the
sequential chunk dimension (the flash-attention accumulator pattern):
initialise at chunk 0, update step-by-step within the chunk, emit outputs
per chunk. HBM traffic is one pass over r/k/v/w and y — the pure-JAX scan
re-materialises the state through HBM every step, which is exactly the
memory-bound hot loop this kernel removes for the rwkv6-1.6b arch.

Validated against the pure-jnp oracle (repro.models.rwkv6.wkv6_scan) in
tests/test_kernels.py over shape/dtype sweeps.

Registry contract: dispatched as ``wkv6`` with tile space {default, tile_bh
4/16, (tile_bh=8, chunk=128)}.  The cross-chunk accumulator is a
``pltpu.VMEM`` scratch — a TPU-only primitive — so the registry lists
``compiled=(tpu-mosaic,)``: on a GPU host dispatch falls back to the jnp
oracle instead of pretending Triton can lower this.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_BH = 8
CHUNK = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref):
    """Block shapes: r/k/v/w (TILE_BH, CHUNK, dh); u (TILE_BH, dh);
    y (TILE_BH, CHUNK, dh); scratch s (TILE_BH, dh, dh)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    chunk = r.shape[1]

    def step(t, carry):
        s, y = carry
        rt, kt, vt, wt = r[:, t], k[:, t], v[:, t], w[:, t]   # (TILE_BH, dh)
        kv = kt[:, :, None] * vt[:, None, :]                   # (TILE_BH, dh, dh)
        yt = jnp.einsum("bk,bkv->bv", rt, s + u[:, :, None] * kv)
        s = jnp.exp(-jnp.exp(wt))[:, :, None] * s + kv
        y = y.at[:, t].set(yt)
        return s, y

    s0 = s_ref[...]
    y0 = jnp.zeros(r.shape, jnp.float32)
    s, y = jax.lax.fori_loop(0, chunk, step, (s0, y0))
    s_ref[...] = s
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_bh", "chunk"))
def wkv6(r, k, v, wlog, u, *, interpret: bool, tile_bh: int = TILE_BH,
         chunk: int = CHUNK):
    """r,k,v,wlog: (B, S, H, dh); u: (H, dh). Returns y (B, S, H, dh).

    The (B, H) axes merge into one parallel tile axis; S splits into
    sequential chunks with the state carried in VMEM scratch.
    """
    B, S, H, dh = r.shape
    BH = B * H

    def to_bh(x):  # (B,S,H,dh) -> (BH, S, dh)
        return jnp.moveaxis(x, 2, 1).reshape(BH, S, dh)

    rb, kb, vb, wb = (to_bh(jnp.asarray(x)) for x in (r, k, v, wlog))
    ub = jnp.broadcast_to(jnp.asarray(u, jnp.float32)[None], (B, H, dh)).reshape(BH, dh)

    pad_bh = (-BH) % tile_bh
    pad_s = (-S) % chunk
    if pad_bh or pad_s:
        padded = lambda x: jnp.pad(x, ((0, pad_bh), (0, pad_s), (0, 0)))
        rb, kb, vb, wb = map(padded, (rb, kb, vb, wb))
        ub = jnp.pad(ub, ((0, pad_bh), (0, 0)))
    BHp, Sp = rb.shape[0], rb.shape[1]

    spec = pl.BlockSpec((tile_bh, chunk, dh), lambda i, j: (i, j, 0))
    y = pl.pallas_call(
        _wkv6_kernel,
        grid=(BHp // tile_bh, Sp // chunk),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((tile_bh, dh), lambda i, j: (i, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((BHp, Sp, dh), rb.dtype),
        scratch_shapes=[pltpu.VMEM((tile_bh, dh, dh), jnp.float32)],
        interpret=interpret,
    )(rb, kb, vb, wb, ub)
    y = y[:BH, :S]
    return jnp.moveaxis(y.reshape(B, H, S, dh), 1, 2)
