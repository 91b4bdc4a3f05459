"""The ``campaign_erf_share.ingest`` reader: the evaluated share of the
campaign kernel's failure terms from the program's counter, and None for a
program without the counter."""
from types import SimpleNamespace

import numpy as np

import harness

READER = "campaign_erf_share.ingest"


def _terms():
    from repro.obs import REGISTRY
    series = REGISTRY.snapshot()["repro_campaign_erf_evals_total"]["series"]
    return {o: sum(s["value"] for s in series if s["labels"]["outcome"] == o)
            for o in ("evaluated", "saturated")}


def test_reader_gives_the_evaluated_share_of_the_kernel_route():
    """On the kernel's route a campaign at 10 ns skips terms: the reader
    reads the counter's evaluated share, above 0 and below 1."""
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import hash_poisson_counts
    from repro.kernels import ops
    batch = synthetic_fleet(2, TINY, seed=1).chunk(0, 2)
    with ops.force_backend("cpu-pallas-interpret"):
        hash_poisson_counts(batch, "trp", 10.0, refresh_ms=256.0)
    n = _terms()
    value = harness.metric_reader(READER).read(SimpleNamespace())
    assert n["saturated"] > 0
    assert np.isclose(value, n["evaluated"] / sum(n.values()))
    assert 0.0 < value < 1.0


def test_reader_reads_none_without_the_counter(monkeypatch):
    from repro.obs import REGISTRY
    snap = REGISTRY.snapshot()
    snap.pop("repro_campaign_erf_evals_total", None)
    monkeypatch.setattr(REGISTRY, "snapshot", lambda: snap)
    assert harness.metric_reader(READER).read(SimpleNamespace()) is None
