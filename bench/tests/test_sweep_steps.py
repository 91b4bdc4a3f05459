"""The ``sweep_steps_share.*`` readers: the evaluated share of the sweep's
grid points from the program's counter, and None for a program without
the counter."""
from types import SimpleNamespace

import numpy as np

import harness

READERS = ("sweep_steps_share.ingest", "sweep_steps_share.tick",
           "sweep_steps_share.paper-mix")


def test_readers_give_the_evaluated_share_of_the_counter():
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.core.substrate import profile_population_arrays
    from repro.obs import REGISTRY
    batch = synthetic_fleet(4, TINY, seed=1).chunk(0, 4)
    profile_population_arrays(batch, multibit_only=True)
    series = REGISTRY.snapshot()["repro_sweep_grid_steps_total"]["series"]
    ev = sum(s["value"] for s in series
             if s["labels"]["outcome"] == "evaluated")
    total = sum(s["value"] for s in series)
    for name in READERS:
        value = harness.metric_reader(name).read(SimpleNamespace())
        assert np.isclose(value, ev / total) and 0.0 < value <= 1.0, name


def test_readers_read_none_without_the_counter(monkeypatch):
    from repro.obs import REGISTRY
    snap = REGISTRY.snapshot()
    snap.pop("repro_sweep_grid_steps_total", None)
    monkeypatch.setattr(REGISTRY, "snapshot", lambda: snap)
    for name in READERS:
        assert harness.metric_reader(name).read(SimpleNamespace()) is None
