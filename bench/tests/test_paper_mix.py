"""The paper-mix ingest cell: every design of the configuration arrives,
its metric readers read what their ``.ingest`` namesakes read (and the
founding counter), and the cell runs correct at a size the CPU holds."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

import fleetcell
import harness
import program_spans as ps
from conftest import shrink

CELL = "ingest.paper-mix"
READERS = ("hit_share.paper-mix", "every_row_busy.paper-mix",
           "campaign_busy.paper-mix", "discover_share.paper-mix",
           "device_idle.paper-mix", "compiles_in_window.paper-mix",
           "one_arm_verified.paper-mix")


@pytest.fixture(scope="module")
def spec():
    return harness.resolve_cell(CELL, harness.load_benchmark())


def test_every_chunk_brings_five_or_six_of_each_design(spec):
    config, traffic = spec["config"], spec["traffic"]
    assert traffic["designs"] == config["designs"]          # all 11
    n = traffic["dimms_per_call"]
    order = fleetcell.arrivals(config, traffic, traffic["arrivals"])
    chunks = order.reshape(-1, n) % len(config["designs"])
    per_design = np.stack([(chunks == d).sum(axis=1)
                           for d in range(len(config["designs"]))])
    assert per_design.min() == 5 and per_design.max() == 6


def test_the_cell_runs_its_own_configuration_whose_pins_hold(spec):
    """``ddr3-fleet-all-dies``: the fleet, geometry and FleetConfig of
    ``ddr3-fleet-paper``, every design arriving, and the one-arm rule
    among its guarantees."""
    config = spec["config"]
    assert spec["cell"]["config"] == config["name"] == "ddr3-fleet-all-dies"
    fleetcell.check_pins(config)
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == list(config["reduced"])
    assert any("one-arm" in g for g in config["guarantees"])


def test_the_cell_lists_its_rate_and_its_own_metrics(spec):
    assert spec["cell"]["chips"] == 1
    assert {m["name"] for m in spec["end_to_end"]} == {"ingest_dimms_per_s",
                                                       "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == list(READERS)


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=[])


def _phases():
    """Synthetic ``program_spans.phases`` of one ingest chunk that founds."""
    ops = [_ev("%a", 100, 300), _ev("%b", 600, 900)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    main = [_ev("bench.ingest_chunk", 50, 1000),
            _ev("serve.ingest", 60, 990),
            _ev("serve.ingest.campaign", 80, 320),
            _ev("serve.ingest.discover", 330, 480),
            _ev("serve.ingest.discover.region", 420, 470),
            _ev("serve.ingest.every_row", 500, 950)]
    host = NS(name="/host:CPU", lines=[NS(name="main", events=main)])
    return ps.phases(NS(planes=[dev, host]))


def _ctx(**kw):
    base = dict(trace={"window_s": 2.0, "busy_s": 1.5},
                counters={"paths": {"hit": 58, "discover": 0,
                                    "conventional": 6}},
                compiles_in_window=0, notes={})
    return NS(**dict(base, **kw))


def test_each_reader_reads_a_value(monkeypatch):
    from repro.obs.metrics import Registry
    reg = Registry()
    fam = reg.counter("repro_serve_found_total", "",
                      ("server", "verified", "region"))
    fam.labels(server="0", verified="true", region="both_arms").inc(11)
    fam.labels(server="0", verified="false", region="one_arm").inc(1)
    monkeypatch.setattr("repro.obs.REGISTRY", reg)
    ph = _phases()
    monkeypatch.setattr(ps, "current", lambda log_dir=None: ph)
    got = {name: harness.metric_reader(name).read(_ctx())
           for name in READERS}
    assert got["hit_share.paper-mix"] == 58 / 64
    assert np.isclose(got["campaign_busy.paper-mix"], 200 / 950)
    assert np.isclose(got["every_row_busy.paper-mix"], 300 / 950)
    assert np.isclose(got["discover_share.paper-mix"], 150 / 950)
    assert got["device_idle.paper-mix"] == 0.25
    assert got["compiles_in_window.paper-mix"] == 0
    assert got["one_arm_verified.paper-mix"] == 0
    fam.labels(server="0", verified="true", region="one_arm").inc()
    assert harness.metric_reader("one_arm_verified.paper-mix").read(
        _ctx()) == 1


def test_readers_read_nothing_where_their_source_is_absent(monkeypatch):
    """A program without the serving spans or the founding counter (one
    older than them): None, never an error.  The compile count is the
    harness's own and always present."""
    from repro.obs.metrics import Registry
    monkeypatch.setattr("repro.obs.REGISTRY", Registry())
    monkeypatch.setattr(ps, "current", lambda log_dir=None: None)
    ctx = _ctx(trace=None, counters={"paths": {}})
    for name in READERS:
        if name != "compiles_in_window.paper-mix":
            assert harness.metric_reader(name).read(ctx) is None, name


def test_the_cell_runs_correct_on_the_cpu(run_cell):
    res = run_cell(CELL, shrink())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
