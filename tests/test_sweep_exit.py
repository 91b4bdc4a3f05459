"""The timing sweep's early exit: ``_sweep_param`` stops walking a grid once
every (DIMM, bank) of the batch has stopped, so it must serve exactly the
tables of a full scan over the grid (the pre-early-exit sweep, copied here
as the reference) while evaluating only the points a table can depend on.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.geometry import SMALL, TINY
from repro.core.latency import DEFAULT_ITERS, worst_rows_internal
from repro.core.population import make_population, synthetic_fleet
from repro.core.streaming import stream_profile_population
from repro.core.substrate import (GRIDS, DimmBatch, _profile_jit,
                                  _region_eval, condition_adders,
                                  pattern_stress, profile_population_arrays)
from repro.core.timing import CYCLE_NS, PARAMS, STANDARD
from repro.obs import REGISTRY
from repro.serve import FleetConfig, FleetServer, oracle_parity

AGES = (0.0, 5.0, 20.0, 80.0)
KW = dict(guard_cycles=1, iters=DEFAULT_ITERS, multibit=True)
STEPS = "repro_sweep_grid_steps_total"


def _scan_sweep_param(batch, pidx, floor, rows, stress, adder, guard_cycles,
                      iters, multibit, banks):
    """The full-scan sweep: every grid point evaluated, the table read off
    the prefix before each (DIMM, bank)'s first stop.  Also returns the
    per-point failures and below-floor flags, (G, D, banks) each."""
    grid = jnp.asarray(GRIDS[PARAMS[pidx]], jnp.float32)
    std = getattr(STANDARD, PARAMS[pidx])

    def step(_, t_op):
        fail, _ = _region_eval(batch, pidx, t_op, rows, stress, adder,
                               iters, multibit, banks)
        return None, (fail, t_op < floor - 1e-9)

    _, (fails, below) = jax.lax.scan(step, None, grid)
    ok = jnp.cumsum((fails | below).astype(jnp.int32), axis=0) == 0
    best = jnp.min(jnp.where(ok, grid[:, None, None], jnp.inf), axis=0)
    best = jnp.where(jnp.isfinite(best), best, std)
    return jnp.minimum(best + guard_cycles * CYCLE_NS, std), fails, below


@functools.partial(jax.jit, static_argnames=("guard_cycles", "iters",
                                             "multibit", "banks"))
def _scan_profile(batch, rows, stress, adder, *, guard_cycles, iters,
                  multibit, banks):
    D = batch.serial.shape[0]
    kw = dict(rows=rows, stress=stress, adder=adder, banks=banks,
              guard_cycles=guard_cycles, iters=iters, multibit=multibit)
    floor5 = jnp.full((D, banks), 5.0, jnp.float32)
    trcd, f0, b0 = _scan_sweep_param(batch, 0, floor5, **kw)
    tras, f1, b1 = _scan_sweep_param(batch, 1, trcd + 10.0, **kw)
    trp, f2, b2 = _scan_sweep_param(batch, 2, floor5, **kw)
    twr, f3, b3 = _scan_sweep_param(batch, 3, floor5, **kw)
    return (jnp.stack([trcd, tras, trp, twr], axis=2),
            (f0, f1, f2, f3), (b0, b1, b2, b3))


def _needed_steps(fails, below) -> int:
    """Grid points a loop must evaluate: per (DIMM, bank), the points down
    to and including its first failure, none at or past its floor; the
    batch maximum of that."""
    fails, below = np.asarray(fails), np.asarray(below)
    G = fails.shape[0]
    first_fail = np.where(fails.any(axis=0), fails.argmax(axis=0), G)
    n_above = (~below).sum(axis=0)
    return int(np.minimum(first_fail + 1, n_above).max())


def _batch(geom, n=5):
    return DimmBatch.from_population(make_population(geom, n))


def _aged(batch, age):
    return dataclasses.replace(
        batch, age_years=np.full(batch.n_dimms, age, np.float32))


def _rows(region, batch):
    if region == "worst":
        return worst_rows_internal(batch.geom)
    if region == "all":
        return np.arange(batch.geom.rows_per_mat)
    rng = np.random.default_rng(7)
    return rng.integers(0, batch.geom.rows_per_mat, (batch.n_dimms, 2))


def _both(batch, rows, banks, **kw):
    """(impl tables, impl steps (4,), scan tables, needed steps (4,))."""
    kw = dict(KW, **kw)
    args = (batch, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pattern_stress()),
            jnp.asarray(condition_adders(batch, 55.0, 64.0)))
    tables, steps = _profile_jit(*args, banks=banks, **kw)
    want, fails, below = _scan_profile(*args, banks=banks, **kw)
    steps = np.asarray(steps)
    assert steps.shape == (batch.n_dimms, len(PARAMS))
    assert (steps == steps[:1]).all()
    needed = [_needed_steps(f, b) for f, b in zip(fails, below)]
    return np.asarray(tables), steps[0], np.asarray(want), needed


@pytest.mark.parametrize("banks", [1, 2])
@pytest.mark.parametrize("region", ["worst", "all", "per_dimm"])
@pytest.mark.parametrize("geom", [TINY, SMALL], ids=["TINY", "SMALL"])
def test_early_exit_tables_match_full_scan(geom, region, banks):
    batch = _batch(geom)
    rows = _rows(region, batch)
    for age in AGES:
        got, steps, want, needed = _both(_aged(batch, age), rows, banks)
        np.testing.assert_array_equal(got, want, err_msg=f"age {age}")
        assert list(steps) == needed, (age, steps, needed)


def test_trip_count_is_the_batch_maximum_of_first_stops():
    """The loop runs the batch's slowest DIMM down to its first stop: a
    batch's count is the maximum of its members' counts alone."""
    batch = _aged(_batch(SMALL, 6), 5.0)
    rows = _rows("worst", batch)
    _, steps, _, _ = _both(batch, rows, 1)
    alone = []
    for d in range(batch.n_dimms):
        one = dataclasses.replace(batch, **{
            f.name: np.asarray(getattr(batch, f.name))[d:d + 1]
            for f in dataclasses.fields(batch) if f.name != "geom"})
        alone.append(_both(one, rows, 1)[1])
    np.testing.assert_array_equal(steps, np.max(alone, axis=0))


def _fast(batch, d, base):
    """Set DIMM ``d``'s base latency of every parameter to ``base`` ns."""
    b = np.array(batch.base)
    b[d] = base
    return dataclasses.replace(batch, base=b)


@pytest.mark.parametrize("multibit", [True, False])
def test_one_dimm_passing_every_point_runs_to_its_floor(multibit):
    """A DIMM that never fails walks every grid point above its floor: 4
    tRCD, the 8 tRAS points at or above tRCD + 10 = 16.25 ns, 4 tRP and 5
    tWR.  The 5 tRAS points under the floor are never evaluated."""
    batch = _fast(_aged(_batch(TINY, 4), 0.0), 2, -50.0)
    rows = _rows("worst", batch)
    got, steps, want, needed = _both(batch, rows, 1, multibit=multibit)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[2, 0], [6.25, 18.75, 6.25, 6.25])
    assert list(steps) == needed == [4, 8, 4, 5]
    tras = np.asarray(GRIDS["tras"])
    assert (tras[:steps[1]] >= 16.25).all() and (tras[steps[1]:] < 16.25).all()


def test_all_dimms_stopping_at_the_first_point_run_one_step_each():
    batch = _aged(_batch(TINY, 4), 0.0)
    batch = dataclasses.replace(
        batch, base=np.full_like(np.asarray(batch.base), 100.0))
    rows = _rows("worst", batch)
    got, steps, want, _ = _both(batch, rows, 2)
    np.testing.assert_array_equal(got, want)
    assert list(steps) == [1, 1, 1, 1]
    std = [getattr(STANDARD, p) for p in PARAMS]
    np.testing.assert_array_equal(got, np.broadcast_to(std, got.shape))


def _steps_counter(entry):
    return {(p, o): REGISTRY.value(STEPS, entry=entry, param=p, outcome=o)
            for p in PARAMS for o in ("evaluated", "skipped")}


@pytest.mark.parametrize("entry", ["profile", "stream_profile"])
def test_step_counter_moves_by_one_grid_per_parameter_and_call(entry):
    batch = _aged(_batch(TINY, 4), 20.0)
    before = _steps_counter(entry)
    if entry == "profile":
        out = profile_population_arrays(batch, multibit_only=True)
        calls = 1
    else:
        out = stream_profile_population(batch, chunk_size=2, collect=True,
                                        multibit_only=True)["tables"]
        calls = 2
    after = _steps_counter(entry)
    want = profile_population_arrays(batch, multibit_only=True)
    np.testing.assert_array_equal(out, want)
    for p in PARAMS:
        ev = after[p, "evaluated"] - before[p, "evaluated"]
        sk = after[p, "skipped"] - before[p, "skipped"]
        assert ev + sk == calls * len(GRIDS[p]), p
        assert 0 < ev <= calls * len(GRIDS[p])
    # past 16.25 ns no tRAS point is ever evaluated
    n_tras = int(np.sum(np.asarray(GRIDS["tras"]) >= 16.25))
    assert after["tras", "skipped"] - before["tras", "skipped"] \
        >= calls * (len(GRIDS["tras"]) - n_tras)


def test_serve_profile_counts_one_grid_per_parameter_and_sweep():
    """The HIT sweep of a clone-padded chunk counts at the host too: one
    grid per parameter for each call of ``serve_profile``, and the served
    tables match the dense oracle (``oracle_parity``)."""
    server = FleetServer(synthetic_fleet(48, TINY, seed=0),
                         FleetConfig(chunk_size=16))
    before = _steps_counter("serve_profile")
    server.ingest(now=5.0)
    after = _steps_counter("serve_profile")
    moved = {p: sum(after[p, o] - before[p, o]
                    for o in ("evaluated", "skipped")) for p in PARAMS}
    calls = moved["trcd"] // len(GRIDS["trcd"])
    assert calls > 0
    assert moved == {p: calls * len(GRIDS[p]) for p in PARAMS}
    assert oracle_parity(server, 48)["parity"]
