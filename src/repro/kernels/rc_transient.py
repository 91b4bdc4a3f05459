"""Pallas TPU kernel: RC-ladder transient integrator (the SPICE-lite hot loop).

Each grid step owns a TILE of cells; the whole Euler time loop runs inside
the kernel with the (TILE, n_seg) ladder state resident in VMEM — the HBM
traffic is one read of the cell parameters and one write of the results,
instead of 4500 time-step roundtrips. This is the DIVA characterization
campaign's compute hot spot (96 DIMMs x per-cell transient fits).

Outputs per cell: v_probe(final), v_cell(final), sense_time (first crossing
of 0.9 V at the cell's tap). Semantics match core/spice.simulate exactly
(same discrete update; validated in tests/test_kernels.py).

Registry contract: dispatched as ``rc_transient`` with tile space {default,
32, 64, 256} over the cell axis.  Per-cell integration is independent, but
this is a float kernel: across DIFFERENT tiles XLA may fuse/contract the
Euler update differently, so cross-tile agreement is ulp-scale, not bitwise
(the fail_prob caveat in ARCHITECTURE 3i) — each fixed tile is deterministic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.spice import CircuitParams

TILE = 128


def _make_kernel(cp: CircuitParams, t_total_ns: float, t_pre_ns: float,
                 v_ready: float, n_seg: int):
    steps = int(t_total_ns / cp.dt_ns)
    c_seg = cp.c_bl_fF / n_seg
    tau_seg = cp.tau_seg_ns
    tau_acc_cell = cp.r_acc_kohm * cp.c_cell_fF * 1e-3
    tau_acc_node = cp.r_acc_kohm * c_seg * 1e-3

    def kernel(tap_oh_ref, twl_ref, vcell0_ref, vp_ref, vc_ref, ts_ref):
        tap_oh = tap_oh_ref[...]          # (TILE, n_seg) one-hot f32
        t_wl = twl_ref[...]               # (TILE, 1)
        v_cell = vcell0_ref[...]          # (TILE, 1)
        v_bl = jnp.full(tap_oh.shape, cp.v_half, jnp.float32)
        t_sense = jnp.full(t_wl.shape, jnp.inf, jnp.float32)

        def body(i, carry):
            v_bl, v_cell, t_sense = carry
            t = i.astype(jnp.float32) * cp.dt_ns
            left = jnp.concatenate([v_bl[:, :1], v_bl[:, :-1]], axis=1)
            right = jnp.concatenate([v_bl[:, 1:], v_bl[:, -1:]], axis=1)
            dv = (left - 2 * v_bl + right) / tau_seg
            wl_on = jax.nn.sigmoid((t - t_wl) / 0.3) * jnp.where(t < t_pre_ns, 1.0, 0.0)
            v_tap = jnp.sum(v_bl * tap_oh, axis=1, keepdims=True)
            dv_cell = wl_on * (v_tap - v_cell) / tau_acc_cell
            dv = dv + tap_oh * (wl_on * (v_cell - v_tap) / tau_acc_node)
            sa_on = jnp.where((t >= cp.sa_enable_ns) & (t < t_pre_ns), 1.0, 0.0)
            v0 = v_bl[:, :1]
            regen = cp.sa_gain_per_ns * jnp.tanh((v0 - cp.v_half) * 25.0) * sa_on
            dv = dv.at[:, :1].add(regen)
            pre = jnp.where(t >= t_pre_ns, 1.0, 0.0)
            dv = dv.at[:, :1].add(pre * (cp.v_half - v0) / cp.precharge_tau_ns)
            v_bl = jnp.clip(v_bl + dv * cp.dt_ns, 0.0, cp.vdd)
            v_cell = jnp.clip(v_cell + dv_cell * cp.dt_ns, 0.0, cp.vdd)
            v_probe = jnp.sum(v_bl * tap_oh, axis=1, keepdims=True)
            t_sense = jnp.where((v_probe >= v_ready) & jnp.isinf(t_sense), t, t_sense)
            return v_bl, v_cell, t_sense

        v_bl, v_cell, t_sense = jax.lax.fori_loop(0, steps, body,
                                                  (v_bl, v_cell, t_sense))
        vp_ref[...] = jnp.sum(v_bl * tap_oh, axis=1, keepdims=True)
        vc_ref[...] = v_cell
        ts_ref[...] = t_sense

    return kernel


@functools.partial(jax.jit, static_argnames=("cp", "t_total_ns", "t_pre_ns",
                                              "v_ready", "interpret", "tile"))
def rc_transient(row_frac, col_frac, *, interpret: bool,
                 cp: CircuitParams = CircuitParams(),
                 t_total_ns: float = 45.0, t_pre_ns: float = 30.0,
                 v_ready: float = 0.9, cell_charged: bool = True,
                 tile: int = TILE):
    """row_frac/col_frac: (N,) in [0,1]. Returns dict(v_probe, v_cell, sense_t)."""
    row_frac = jnp.asarray(row_frac, jnp.float32).reshape(-1)
    col_frac = jnp.broadcast_to(jnp.asarray(col_frac, jnp.float32).reshape(-1),
                                row_frac.shape)
    n = row_frac.shape[0]
    pad = (-n) % tile
    if pad:
        row_frac = jnp.pad(row_frac, (0, pad))
        col_frac = jnp.pad(col_frac, (0, pad))
    n_seg = cp.n_seg
    tap = jnp.clip(jnp.round(row_frac * (n_seg - 1)).astype(jnp.int32), 0, n_seg - 1)
    tap_oh = jax.nn.one_hot(tap, n_seg, dtype=jnp.float32)
    t_wl = (col_frac * cp.wl_delay_ns_max)[:, None]
    v_cell0 = jnp.full((row_frac.shape[0], 1), cp.vdd if cell_charged else 0.0,
                       jnp.float32)
    N = row_frac.shape[0]
    kern = _make_kernel(cp, t_total_ns, t_pre_ns, v_ready, n_seg)
    vp, vc, ts = pl.pallas_call(
        kern,
        grid=(N // tile,),
        in_specs=[pl.BlockSpec((tile, n_seg), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, 1), jnp.float32)],
        interpret=interpret,
    )(tap_oh, t_wl, v_cell0)
    return {"v_probe": vp[:n, 0], "v_cell": vc[:n, 0], "sense_t": ts[:n, 0]}
