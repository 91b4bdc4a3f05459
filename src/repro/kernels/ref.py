"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecc as _ecc
from repro.core import spice as _spice
from repro.kernels import shuffle as _shuffle_mod
from repro.models.rwkv6 import wkv6_scan as _wkv6_scan


def fail_prob(row_src, d_mat, coeffs, *, cols: int, open_bitline: bool = True,
              voltage: bool = False, retention: bool = False,
              reduce_rows: bool = False):
    """(M, R, C) failure-probability grid — pure-jnp oracle of the Pallas
    kernel in kernels/fail_prob.py (same formula helper, same bits), with
    the same ``voltage``/``retention`` channel flags.  With ``reduce_rows``,
    the grid's (R,) sum over mats and columns."""
    from repro.kernels.fail_prob import cell_probs
    row_src = jnp.asarray(row_src, jnp.int32)
    d_mat = jnp.asarray(d_mat, jnp.float32)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    R = row_src.shape[0]
    rf = jnp.broadcast_to(row_src.astype(jnp.float32)[None, :, None],
                          (d_mat.shape[0], R, cols))
    colf = jax.lax.broadcasted_iota(jnp.float32, (d_mat.shape[0], R, cols), 2)
    even = (jax.lax.broadcasted_iota(jnp.int32, (d_mat.shape[0], R, cols), 2)
            % 2) == 0
    grid = cell_probs(rf, colf, even, d_mat[:, None, None], coeffs, R, cols,
                      open_bitline, voltage, retention)
    return grid.sum(axis=(0, 2)) if reduce_rows else grid


def bank_sched(q_bank, q_row, q_write, q_arrive, q_valid,
               open_row, ready, pre_ready, bus_ready, last_act, faw_old,
               t_now, tc, bank_rank, bank_chan, *,
               tbl: int, trrd: int, tfaw: int, use_bus: bool, use_act: bool):
    """FR-FCFS candidate scoring — pure-jnp oracle of the Pallas kernel in
    kernels/bank_sched.py (same ``candidate_times`` formula helper; all-int32
    arithmetic, so oracle, kernel, and the NumPy reference walker in
    memsim/reference.py agree value-for-value)."""
    from repro.kernels.bank_sched import candidate_times
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return candidate_times(
        i32(q_bank), i32(q_row), i32(q_write), i32(q_arrive),
        jnp.asarray(q_valid).astype(bool), i32(open_row), i32(ready),
        i32(pre_ready), i32(bus_ready), i32(last_act), i32(faw_old),
        i32(t_now).reshape(()), i32(tc), i32(bank_rank), i32(bank_chan),
        tbl=tbl, trrd=trrd, tfaw=tfaw, use_bus=use_bus, use_act=use_act,
        xp=jnp)


def bit_signature(counts, nbits: int):
    """(N, R) int32 counts -> (N, nbits) int32 per-address-bit
    (sum over rows with the bit set) - (sum with it clear) — pure-jnp oracle
    of kernels/bit_signature.py.  Integer reduction: exact and order
    independent, so oracle, kernel and the NumPy reference
    (``core/mapping._signature_sums``) agree value-for-value."""
    counts = jnp.asarray(counts, jnp.int32)
    r = jnp.arange(counts.shape[-1], dtype=jnp.int32)
    pm = ((r[None, :] >> jnp.arange(nbits, dtype=jnp.int32)[:, None]) & 1) \
        * 2 - 1                                          # (nbits, R) in ±1
    return jnp.sum(counts[:, None, :] * pm[None, :, :], axis=-1)


def secded_encode(data_bits):
    """(N, 64) -> (N, 8) check bits."""
    code = _ecc.encode(data_bits)
    return code[:, _ecc.DATA_BITS:]


def secded_syndrome(code_bits):
    return _ecc.syndrome(code_bits)


def diva_shuffle(bursts, inverse: bool = False, shuffle: bool = True,
                 perm: np.ndarray | None = None):
    if perm is None:
        perm = _shuffle_mod.shuffle_permutation(shuffle)
    perm = np.asarray(perm, np.int32)
    bursts = jnp.asarray(bursts, jnp.int32)
    if inverse:
        inv = np.zeros_like(perm)
        inv[perm] = np.arange(len(perm))
        return bursts[:, inv]
    return bursts[:, perm]


def rc_transient(row_frac, col_frac, *, cp=_spice.CircuitParams(),
                 t_total_ns: float = 45.0, t_pre_ns: float = 30.0,
                 v_ready: float = 0.9, cell_charged: bool = True, **_):
    res = _spice.simulate(jnp.asarray(row_frac).reshape(-1),
                          jnp.asarray(col_frac).reshape(-1),
                          t_total_ns=t_total_ns, t_precharge_at_ns=t_pre_ns,
                          cp=cp, cell_charged=cell_charged)
    sense = _spice.sense_time(res, v_ready)
    return {"v_probe": np.asarray(res["v_probe"])[..., -1],
            "v_cell": np.asarray(res["v_cell"])[..., -1],
            "sense_t": sense}


def wkv6(r, k, v, wlog, u):
    y, _ = _wkv6_scan(jnp.asarray(r), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(wlog), jnp.asarray(u, jnp.float32))
    return y
