"""The program's phase spans in a trace: interval arithmetic on a synthetic
profile, the readers on it, and the whole reading of a ``FleetServer``
trace recorded on the CPU."""
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import harness
import program_spans as ps

INGEST_READERS = ("campaign_busy.ingest", "sweep_busy.ingest",
                  "every_row_busy.ingest", "discover_share.ingest",
                  "server_host_share.ingest")
TICK_READERS = ("sweep_busy.tick", "every_row_busy.tick",
                "server_host_share.tick")


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=[])


def _profile():
    # device busy [100, 300), [350, 400) with a nested op, [600, 900)
    ops = [_ev("%a", 100, 300), _ev("%b", 150, 250), _ev("%c", 350, 400),
           _ev("%d", 600, 900)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    main = [_ev("bench.ingest_chunk", 50, 1000),
            _ev("serve.ingest", 60, 990),
            _ev("serve.ingest.campaign", 80, 320),
            _ev("serve.ingest.hit_sweep", 500, 950),
            _ev("serve.ingest.discover", 330, 480),
            _ev("serve.ingest.discover.vote", 340, 420),
            _ev("PjitFunction(x)", 90, 95)]
    host = NS(name="/host:CPU", lines=[NS(name="main", events=main)])
    return NS(planes=[dev, host])


def test_busy_idle_and_self_time_of_each_span():
    ph = ps.phases(_profile())
    assert np.isclose(ph["window_s"], 950e-9)
    sp = ph["spans"]
    assert set(sp) == {"serve.ingest", "serve.ingest.campaign",
                       "serve.ingest.hit_sweep", "serve.ingest.discover",
                       "serve.ingest.discover.vote"}
    camp = sp["serve.ingest.campaign"]
    assert np.isclose(camp["busy_s"], 200e-9)          # [100, 300)
    assert np.isclose(camp["idle_s"], 40e-9)
    assert np.isclose(sp["serve.ingest.discover.vote"]["busy_s"], 50e-9)
    assert np.isclose(sp["serve.ingest.hit_sweep"]["busy_s"], 300e-9)
    ingest = sp["serve.ingest"]
    assert np.isclose(ingest["busy_s"], 550e-9)
    # self time leaves out the direct children, not the grandchild twice
    assert np.isclose(ingest["self_s"], (930 - 240 - 450 - 150) * 1e-9)
    assert np.isclose(sp["serve.ingest.discover"]["self_s"], 70e-9)
    for f in sp.values():
        assert np.isclose(f["busy_s"] + f["idle_s"], f["seconds"])
        assert f["count"] == 1


def test_spans_are_clipped_to_the_bench_window():
    prof = _profile()
    prof.planes[1].lines[0].events[0] = _ev("bench.ingest_chunk", 200, 1000)
    sp = ps.phases(prof)["spans"]
    camp = sp["serve.ingest.campaign"]
    assert np.isclose(camp["seconds"], 120e-9)         # [200, 320)
    assert np.isclose(camp["busy_s"], 100e-9)          # [200, 300)
    assert np.isclose(sp["serve.ingest"]["seconds"], 790e-9)


def test_share_reads_none_without_the_program_spans_and_zero_for_a_quiet_phase():
    ph = ps.phases(_profile())
    assert ps.share("busy_s", ("serve.tick.every_row",), "serve.tick",
                    ph) is None
    assert ps.share("busy_s", ("serve.ingest.every_row",), "serve.ingest",
                    ph) == 0.0
    both = ps.share("busy_s", ("serve.ingest.campaign",
                               "serve.ingest.hit_sweep"), "serve.ingest", ph)
    assert np.isclose(both, 500 / 950)


def test_newest_trace_is_the_one_last_written(tmp_path):
    assert ps.newest_trace(tmp_path) is None
    old = tmp_path / "a-1" / "x.xplane.pb"
    new = tmp_path / "b-0" / "y.xplane.pb"
    for p in (new, old):
        p.parent.mkdir()
        p.write_bytes(b"")
    os.utime(old, ns=(1, 1))
    assert ps.newest_trace(tmp_path) == new


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """A small FleetServer's founding ingest and a tick, traced on the CPU
    inside ``bench.*`` annotations, read as the readers read a run."""
    import jax
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.serve import FleetConfig, FleetServer
    server = FleetServer(synthetic_fleet(12, TINY, seed=3),
                         FleetConfig(chunk_size=6))
    server.ingest(6, now=0.0)                          # compile
    server.tick(100.0)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation("bench.ingest_chunk"):
            server.ingest(6, now=0.0)
        with jax.profiler.TraceAnnotation("bench.tick"):
            server.tick(200.0)
    import trace_reduce
    prof = trace_reduce.load(trace_reduce.find_trace(log_dir))
    kw = dict(plane_prefix="/host:CPU", op_line="tf_XLA*")
    return ps.phases(prof, **kw), trace_reduce.reduce(prof, **kw), log_dir


def test_a_fleet_trace_recorded_on_the_cpu(served_trace):
    ph, red, _ = served_trace
    assert np.isclose(ph["window_s"], red["window_s"])
    sp = ph["spans"]
    assert {"serve.ingest", "serve.ingest.campaign", "serve.ingest.match",
            "serve.ingest.hit_sweep", "serve.tick", "serve.tick.drain",
            "serve.tick.hit_sweep"} <= set(sp)
    for name, f in sp.items():
        assert np.isclose(f["busy_s"] + f["idle_s"], f["seconds"]), name
        assert -1e-12 <= f["self_s"] <= f["seconds"] + 1e-12, name
    children = sum(f["seconds"] for n, f in sp.items()
                   if n.startswith("serve.ingest.") and n.count(".") == 2)
    assert np.isclose(sp["serve.ingest"]["self_s"],
                      sp["serve.ingest"]["seconds"] - children)
    idle = sp["serve.ingest"]["idle_s"] + sp["serve.tick"]["idle_s"]
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9


def test_the_run_trace_is_parsed_once(served_trace):
    _, _, log_dir = served_trace
    first = ps.current(log_dir)
    assert "serve.tick" in first["spans"]
    assert ps.current(log_dir) is first


def test_every_reader_gives_a_share(served_trace, monkeypatch):
    ph, _, log_dir = served_trace
    monkeypatch.setattr(ps, "current", lambda log_dir=log_dir: ph)
    for name in INGEST_READERS + TICK_READERS:
        value = harness.metric_reader(name).read(NS())
        assert value is not None and 0.0 <= value <= 1.0, name
