"""The plain references agree with the program's own reference walkers
at sizes the CPU holds (they import nothing of the program; the tests
do)."""
import dataclasses

import numpy as np
import pytest

from reference import fleet as fleet_ref


def _fleet(n, geom_name="TINY", seed=123):
    from repro.core import geometry as geo
    from repro.core.population import synthetic_fleet
    g = getattr(geo, geom_name)
    return g, synthetic_fleet(n, g, seed=seed).chunk(0, n)


def _tables(g, batch, rows, age=0.0, dtype=None):
    import jax.numpy as jnp
    lv = {k: np.asarray(getattr(batch, k)) for k in fleet_ref.LEAVES}
    return fleet_ref.tables(lv, rows, age_years=age, temp_C=55.0,
                            refresh_ms=64.0, guard_cycles=1, multibit=True,
                            cols=g.cols_per_mat, mats=g.mats_x,
                            chips=g.chips, block=8,
                            dtype=dtype or jnp.float32)


def test_grid_is_the_papers():
    assert fleet_ref.timing_grid(13.75).tolist() == [12.5, 10.0, 7.5, 5.0]
    assert fleet_ref.timing_grid(35.0)[0] == 35.0
    assert len(fleet_ref.timing_grid(35.0)) == 13


def test_fleet_reference_matches_the_numpy_walker():
    from repro.core.population import fleet_dimm_model
    from repro.core.profiling import diva_profile_loop
    g, batch = _fleet(6)
    ref = _tables(g, batch, fleet_ref.design_worst_rows(g.rows_per_mat))
    for i in range(6):
        tp = diva_profile_loop(fleet_dimm_model(batch, i), temp_C=55.0)
        assert ref[i].tolist() == [tp.trcd, tp.tras, tp.trp, tp.twr]


@pytest.mark.parametrize("region", ["worst", "all"])
@pytest.mark.parametrize("age", [0.0, 2.5])
def test_fleet_reference_matches_the_dense_sweep(region, age):
    from repro.core.substrate import profile_population_arrays
    g, batch = _fleet(24)
    aged = dataclasses.replace(batch, age_years=np.full(24, age, np.float32))
    want = np.asarray(profile_population_arrays(
        aged, region=region, temp_C=55.0, refresh_ms=64.0, guard_cycles=1,
        multibit_only=True), np.float32)[:, :4]
    rows = fleet_ref.design_worst_rows(g.rows_per_mat) if region == "worst" \
        else np.arange(g.rows_per_mat)
    np.testing.assert_array_equal(_tables(g, batch, rows, age), want)
