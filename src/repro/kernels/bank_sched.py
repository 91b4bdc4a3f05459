"""Pallas TPU kernel: FR-FCFS candidate scoring + projected service times.

The memsim scheduler (repro/memsim) walks a request trace with a bounded
queue; every scan step scores the queued candidates — row-hit-first,
oldest-first, arrived-requests-first — and projects each candidate's service
timeline (ACTIVATE under tRP/tRRD/tFAW, column access under tRCD/tCL/tCWL,
data transfer under the per-channel bus with tBL) from the per-bank state and
the candidate bank's OWN timing row.  That per-step candidate computation is
this kernel: one program owns the (Q,) queue slabs and (B,) bank-state slabs
in VMEM and emits (Q,) int32 score/time vectors.

All arithmetic is int32 (cycles) and every per-candidate bank/rank/channel
lookup is a one-hot masked reduction built from an in-kernel iota — exact,
order independent, no dynamic gathers.  The formula lives in
``candidate_times`` (xp-parameterized, the ``fail_prob.cell_probs``
convention) so the kernel body, the pure-jnp oracle (``kernels/ref.py``) and
the NumPy reference walker (``memsim/reference.py``) compute literally the
same values — the foundation of memsim's jitted-vs-loop bit-parity story.

The call is vmap-able over leading axes (the (timing-table x workload) grid
of ``memsim.system_speedup_population``) the same way ``fail_prob`` is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

#: output names, in order, of ``candidate_times`` / the kernel
OUTPUTS = ("key", "hit", "t_act", "t_col", "done", "new_pre", "latency")


def _onehot_gather(table, idx, n: int, xp):
    """Exact int32 gather ``table[idx]`` as a masked one-hot reduction —
    identical bits from numpy, jnp, and inside the kernel (no dynamic
    indexing, Mosaic-safe)."""
    if xp is np:
        iota = np.arange(n, dtype=np.int32)[None, :]
    else:
        iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], n), 1)
    oh = idx[:, None] == iota
    return xp.sum(xp.where(oh, table[None, :], xp.int32(0)), axis=1,
                  dtype=xp.int32)


def candidate_times(q_bank, q_row, q_write, q_arrive, q_valid,
                    open_row, ready, pre_ready, bus_ready, last_act, faw_old,
                    t_now, tc, bank_rank, bank_chan, *,
                    tbl: int, trrd: int, tfaw: int,
                    use_bus: bool, use_act: bool, xp=jnp):
    """Per-candidate FR-FCFS scoring and service projection; all int32.

    Queue slabs are (Q,); bank state (B,); ``tc`` (B, 6) per-bank cycles in
    [tRCD, tRAS, tRP, tWR, tCL, tCWL] order; ``bus_ready`` (C,) per channel;
    ``last_act``/``faw_old`` (R,) per rank (most recent ACT / oldest of the
    last four ACTs); ``t_now`` the scheduler clock (shape (1,) or scalar).

    Returns ``OUTPUTS``-ordered (Q,) arrays:
      * ``key``     — arbitration priority: 0 invalid slot, 1 valid but not
                      yet arrived, 2 arrived row-miss, 3 arrived row-hit
                      (FR-FCFS: row-hit first; ties broken oldest-first by
                      the caller on (arrive, trace index));
      * ``hit``     — open-row hit (0/1);
      * ``t_act``   — projected ACTIVATE issue time (miss path), respecting
                      tRP after precharge-ready plus — when ``use_act`` —
                      tRRD since the rank's last ACT and tFAW since its
                      fourth-last;
      * ``t_col``   — column command time (``start`` on a hit);
      * ``done``    — data-transfer completion; when ``use_bus`` the transfer
                      waits for the channel bus and occupies it for tBL;
      * ``new_pre`` — the bank's next precharge-ready time (tRAS after ACT;
                      a write folds tWR in after ``done``);
      * ``latency`` — ``done - arrive``.

    With ``use_bus=use_act=False`` the projection is exactly the retained
    in-order walker's service rule (``ramlite._sim_one``): the queue=1
    configuration reproduces it request for request.
    """
    n_banks = int(open_row.shape[0])
    n_ranks = int(last_act.shape[0])
    n_chans = int(bus_ready.shape[0])
    g = lambda table: _onehot_gather(table, q_bank, n_banks, xp)

    orow, rdy, prer = g(open_row), g(ready), g(pre_ready)
    trcd, tras, trp = g(tc[:, 0]), g(tc[:, 1]), g(tc[:, 2])
    twr, tcl, tcwl = g(tc[:, 3]), g(tc[:, 4]), g(tc[:, 5])

    start = xp.maximum(q_arrive, rdy)
    hit = orow == q_row
    pre_ok = xp.maximum(start, prer)
    t_act = pre_ok + trp
    if use_act:
        rank = g(bank_rank)
        la = _onehot_gather(last_act, rank, n_ranks, xp)
        fo = _onehot_gather(faw_old, rank, n_ranks, xp)
        t_act = xp.maximum(t_act, xp.maximum(la + xp.int32(trrd),
                                             fo + xp.int32(tfaw)))
    t_col = xp.where(hit, start, t_act + trcd)
    is_wr = q_write == 1
    data_av = t_col + xp.where(is_wr, tcwl, tcl)
    if use_bus:
        br = _onehot_gather(bus_ready, g(bank_chan), n_chans, xp)
        done = xp.maximum(data_av, br) + xp.int32(tbl)
    else:
        done = data_av
    latency = done - q_arrive
    base_pre = xp.where(hit, prer, t_act + tras)
    new_pre = xp.where(is_wr, xp.maximum(base_pre, done + twr), base_pre)

    validi = q_valid.astype(xp.int32)
    elig = (q_arrive <= t_now).astype(xp.int32)
    hiti = (hit & q_valid).astype(xp.int32)
    key = validi * (1 + elig * (1 + hiti))
    return key, hit.astype(xp.int32), t_act, t_col, done, new_pre, latency


def _make_kernel(statics: dict):
    def kernel(q_bank, q_row, q_write, q_arrive, q_valid,
               open_row, ready, pre_ready, bus_ready, last_act, faw_old,
               t_now, tc, bank_rank, bank_chan, *outs):
        res = candidate_times(
            q_bank[0], q_row[0], q_write[0], q_arrive[0], q_valid[0] != 0,
            open_row[0], ready[0], pre_ready[0], bus_ready[0], last_act[0],
            faw_old[0], t_now[0, 0], tc[...], bank_rank[0], bank_chan[0],
            xp=jnp, **statics)
        for o_ref, val in zip(outs, res):
            o_ref[...] = val[None]

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "tbl", "trrd", "tfaw", "use_bus", "use_act", "q_tile", "interpret"))
def bank_sched(q_bank, q_row, q_write, q_arrive, q_valid,
               open_row, ready, pre_ready, bus_ready, last_act, faw_old,
               t_now, tc, bank_rank, bank_chan, *,
               tbl: int, trrd: int, tfaw: int,
               use_bus: bool, use_act: bool, interpret: bool,
               q_tile: int | None = None):
    """One scheduler step's candidate scoring as a Pallas call; see
    ``candidate_times`` for shapes/semantics.  ``t_now`` is an int32 scalar
    or (1,) array.

    ``q_tile`` tiles the queue axis: the five (Q,) queue slabs and the seven
    (Q,) outputs split into per-tile blocks while the bank/rank/channel state
    broadcasts to every tile (full-array blocks at index 0).  Padded slots
    carry ``q_valid=0``, so their arbitration key is 0 and they are sliced
    off — per-candidate scoring is independent, so results are exact-int
    identical at any tile (the tile-invariance contract).
    """
    statics = dict(tbl=tbl, trrd=trrd, tfaw=tfaw,
                   use_bus=use_bus, use_act=use_act)
    q = int(q_bank.shape[0])
    tile = q if q_tile is None else q_tile
    pad = (-q) % tile
    # every vector travels as a (1, n) row: the memsim grid vmaps this call,
    # which prepends squeezed block dims, and Mosaic accepts the trailing
    # (1, block) only when the 1 is the array's own leading extent
    row = lambda v: jnp.asarray(v, jnp.int32).reshape(1, -1)
    padq = lambda v: jnp.pad(row(v), ((0, 0), (0, pad))) if pad else row(v)
    args = (padq(q_bank), padq(q_row), padq(q_write), padq(q_arrive),
            padq(jnp.asarray(q_valid).astype(jnp.int32)), row(open_row),
            row(ready), row(pre_ready), row(bus_ready), row(last_act),
            row(faw_old), row(t_now), jnp.asarray(tc, jnp.int32),
            row(bank_rank), row(bank_chan))
    qp = q + pad
    q_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    out = pl.pallas_call(
        _make_kernel(statics),
        grid=(qp // tile,),
        in_specs=[q_spec] * 5 + [full(a) for a in args[5:]],
        out_specs=[q_spec] * len(OUTPUTS),
        out_shape=tuple(jax.ShapeDtypeStruct((1, qp), jnp.int32)
                        for _ in OUTPUTS),
        interpret=interpret,
    )(*args)
    return tuple(o[0, :q] for o in out)
