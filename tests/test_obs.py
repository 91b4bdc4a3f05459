"""Observability layer (repro.obs): the zero-interference contract.

Instrumentation lives strictly at host boundaries, so it must be invisible
to the computation: enabled-vs-disabled runs are BITWISE identical on the
profiling substrate, the streamed scans, and the fleet server
(test_*_bit_parity), and running under a profiler trace adds ZERO
compiled programs beyond the warmed cache
(test_no_new_compiles_under_tracing).  The rest pins the data plane
itself: histogram percentile math, label handling, the Prometheus text
exposition, spans in the profiler trace (exact names, nesting, the serve
phases), the memsim compat shim, and the serve-layer ``metrics()``
consistency.
"""
import json
import math
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import streaming as st
from repro.core import substrate
from repro.core.geometry import TINY
from repro.core.population import synthetic_fleet
from repro.core.substrate import profile_population_arrays
from repro.obs.metrics import Registry
from repro.serve import FleetConfig, FleetServer

D, CHUNK = 12, 5             # 5 does not divide 12: exercises the ragged tail
FLEET = synthetic_fleet(D, TINY, seed=3)
BATCH = FLEET.materialize()


@pytest.fixture
def registry():
    """A private Registry — data-plane tests must not touch the global."""
    return Registry()


# ------------------------------------------------------------- data plane

def test_counter_gauge_labels(registry):
    c = registry.counter("repro_test_events_total", "ev", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(3)
    c.labels(kind="b").inc()
    assert c.value(kind="a") == 4 and c.value(kind="b") == 1
    assert registry.value("repro_test_events_total", kind="a") == 4
    assert registry.value("repro_test_events_total", kind="zzz") == 0  # absent
    g = registry.gauge("repro_test_depth")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value() == 3.0
    with pytest.raises(ValueError):
        c.inc()                       # family with labels is not a leaf
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        registry.gauge("repro_test_events_total")   # kind clash


def test_counter_monotone_and_name_validation(registry):
    c = registry.counter("repro_test_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        registry.counter("9starts_with_digit")
    with pytest.raises(ValueError):
        registry.counter("has-dash")


def test_histogram_percentiles_exact_extremes(registry):
    h = registry.histogram("repro_test_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 8.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(14.5)
    # extremes are tracked exactly, interior is bucket-interpolated
    assert h.percentile(0.0) == pytest.approx(0.5)
    assert h.percentile(100.0) == pytest.approx(8.0)
    assert 1.0 <= h.percentile(50.0) <= 2.0
    s = h.summary()
    assert s["count"] == 5 and s["min"] == 0.5 and s["max"] == 8.0
    assert s["mean"] == pytest.approx(14.5 / 5)
    assert math.isnan(
        registry.histogram("repro_empty_seconds").percentile(50.0))


def test_histogram_cumulative_buckets(registry):
    h = registry.histogram("repro_test_cum_seconds", buckets=(1.0, 2.0))
    for v in (0.5, 0.7, 1.5, 9.0):
        h.observe(v)
    assert h._cum_counts() == [2, 3, 4]        # le=1, le=2, le=+Inf


def test_disabled_registry_freezes_all_kinds(registry):
    c = registry.counter("repro_test_total")
    g = registry.gauge("repro_test_g")
    h = registry.histogram("repro_test_h_seconds")
    c.inc(); g.set(5); h.observe(1.0)
    registry.enabled = False
    c.inc(100); g.set(99); h.observe(50.0)
    assert c.value() == 1 and g.value() == 5.0 and h.count == 1
    registry.enabled = True
    c.inc()
    assert c.value() == 2


def test_reset_keeps_handles_live(registry):
    c = registry.counter("repro_test_total", "", ("k",))
    child = c.labels(k="x")
    child.inc(7)
    registry.reset()
    assert child.value() == 0
    child.inc()                       # the held handle still works
    assert c.value(k="x") == 1


def test_prometheus_text_format(registry):
    c = registry.counter("repro_test_events_total", "events", ("path",))
    c.labels(path="hit").inc(3)
    h = registry.histogram("repro_test_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = registry.prometheus_text()
    assert "# HELP repro_test_events_total events\n" in text
    assert "# TYPE repro_test_events_total counter\n" in text
    assert 'repro_test_events_total{path="hit"} 3\n' in text
    assert "# TYPE repro_test_lat_seconds histogram\n" in text
    assert 'repro_test_lat_seconds_bucket{le="0.1"} 1\n' in text
    assert 'repro_test_lat_seconds_bucket{le="1"} 1\n' in text
    assert 'repro_test_lat_seconds_bucket{le="+Inf"} 2\n' in text
    assert "repro_test_lat_seconds_sum 5.05\n" in text
    assert text.endswith("repro_test_lat_seconds_count 2\n")


def test_snapshot_round_trips_through_json(registry):
    registry.counter("repro_test_total").inc(2)
    registry.histogram("repro_test_seconds").observe(0.25)
    snap = json.loads(json.dumps(registry.snapshot()))
    assert snap["repro_test_total"]["kind"] == "counter"
    assert snap["repro_test_total"]["series"][0]["value"] == 2
    assert snap["repro_test_seconds"]["series"][0]["count"] == 1


# ---------------------------------------------------------------- tracing

def _host_spans(log_dir, prefix: str) -> list[tuple]:
    """(name, start_ns, end_ns, stats) of every host event of the newest
    profiler trace under ``log_dir`` whose name starts with ``prefix``."""
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    prof = jax.profiler.ProfileData.from_file(str(path))
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats))
                  for plane in prof.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(prefix))


def _inside(child: tuple, parent: tuple) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_span_records_chrome_events_only_while_tracing(tmp_path):
    """Nested spans are events of the profiler trace under exactly their
    names, the inner inside the outer, args as metadata; a span outside
    the session is not recorded."""
    with obs.span("test.before"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("test.outer", key="v", n=3) as sp:
            with obs.span("test.outer.inner") as inner:
                inner.set(late=7)
    with obs.span("test.after_stop"):
        pass
    assert sp.duration_s > 0
    spans = _host_spans(tmp_path, "test.")
    assert [s[0] for s in spans] == ["test.outer", "test.outer.inner"]
    outer, inner = spans
    assert _inside(inner, outer) and inner[2] > inner[1]
    assert outer[3] == {"key": "v", "n": 3}
    assert inner[3] == {"late": 7}


def test_trace_out_writes_chrome_json_with_spans_and_xla(tmp_path):
    """``launch/serve.py --trace-out F`` (``obs.record``) writes Chrome
    trace-event JSON holding the serving phase spans, with their args, and
    the XLA runtime's own events."""
    from repro.launch.serve import main
    path = tmp_path / "trace.json"
    main(["--fleet", "8", "--chunk", "4", "--trace-out", str(path)])
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    chunks = [e for e in events if e["name"] == "serve.ingest"]
    assert [e["args"]["lo"] for e in chunks] == ["0", "4"]
    assert all(e["dur"] > 0 for e in chunks)
    assert {"serve.fleet_ingest", "serve.ingest.campaign",
            "serve.ingest.hit_sweep"} <= {e["name"] for e in events}
    assert any(not e["name"].startswith("serve.") for e in events)


def test_span_observes_into_histogram(registry):
    h = registry.histogram("repro_test_span_seconds")
    with obs.span("test.timed", hist=h):
        pass
    assert h.count == 1 and h.sum >= 0


INGEST_PHASES = {"serve.ingest", "serve.ingest.campaign",
                 "serve.ingest.signature", "serve.ingest.match",
                 "serve.ingest.discover", "serve.ingest.discover.recover",
                 "serve.ingest.discover.vote",
                 "serve.ingest.discover.region", "serve.ingest.route",
                 "serve.ingest.hit_sweep", "serve.ingest.commit"}
TICK_PHASES = {"serve.tick", "serve.tick.drain", "serve.tick.gather",
               "serve.tick.hit_sweep", "serve.tick.commit"}


def test_fleet_server_phase_spans_nest_in_the_profiler_trace(tmp_path):
    """An ingest that founds generations and a tick that re-profiles the
    whole fleet emit every phase span, each child inside a span of its
    parent's name; each phase observes into repro_serve_phase_seconds."""
    server = FleetServer(FLEET, FleetConfig(chunk_size=CHUNK))
    server.ingest(CHUNK, now=0.0)                       # warm, founds
    with jax.profiler.trace(str(tmp_path)):
        first = server.ingest(CHUNK, now=0.0)
        server.ingest(now=0.0)
        ticked = server.tick(100.0)
    assert ticked["reprofiled"] == D
    spans = _host_spans(tmp_path, "serve.")
    names = {s[0] for s in spans}
    assert INGEST_PHASES | TICK_PHASES <= names
    assert names <= INGEST_PHASES | TICK_PHASES | {
        "serve.ingest.every_row", "serve.tick.every_row"}
    for child in spans:
        parent = child[0].rpartition(".")[0]
        if parent == "serve":
            continue
        assert any(p[0] == parent and _inside(child, p) for p in spans), \
            child[0]
    chunks = [s for s in spans if s[0] == "serve.ingest"]
    assert len(chunks) == 2                             # [5, 10), [10, 12)
    assert chunks[0][3]["lo"] == CHUNK and chunks[0][3]["hi"] == 2 * CHUNK
    founding = [c for c in chunks if any(
        s[0] == "serve.ingest.discover" and _inside(s, c) for s in spans)]
    assert founding and first["n_generations"] > 0
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == 1 and ticks[0][3]["reprofiled"] == D
    drain = next(s for s in spans if s[0] == "serve.tick.drain")
    assert ticks[0][1] <= drain[1] and drain[2] <= min(
        s[1] for s in spans if s[0] == "serve.tick.gather")
    phase_hist = obs.REGISTRY.get("repro_serve_phase_seconds")
    for name in TICK_PHASES:           # the warm ingest ticked nothing
        hist = phase_hist.labels(server=server._sid, phase=name)
        assert hist.count == sum(s[0] == name for s in spans), name
    for name in INGEST_PHASES:         # the warm ingest counts too
        hist = phase_hist.labels(server=server._sid, phase=name)
        assert hist.count >= sum(s[0] == name for s in spans), name


# ------------------------------------------------- the bit-parity contract

def _profile_disabled_then_enabled(fn):
    obs.disable()
    try:
        off = fn()
    finally:
        obs.enable()
    with tempfile.TemporaryDirectory() as tmp, jax.profiler.trace(tmp):
        on = fn()
    return off, on


def test_profile_substrate_bit_parity():
    fn = lambda: np.asarray(profile_population_arrays(BATCH))
    off, on = _profile_disabled_then_enabled(fn)
    assert off.dtype == on.dtype and np.array_equal(off, on)


def test_stream_profile_bit_parity():
    fn = lambda: st.stream_profile_population(
        FLEET, chunk_size=CHUNK, collect=True)
    off, on = _profile_disabled_then_enabled(fn)
    assert np.array_equal(off["tables"], on["tables"])
    for key in ("tables_min", "tables_max"):
        assert np.array_equal(off[key]["value"], on[key]["value"])
        assert np.array_equal(off[key]["serial"], on[key]["serial"])


def test_fleet_server_bit_parity():
    def fn():
        server = FleetServer(FLEET, FleetConfig(chunk_size=CHUNK))
        server.ingest(now=0.0)
        return server
    off, on = _profile_disabled_then_enabled(fn)
    for field in ("serial", "table", "label", "path"):
        assert np.array_equal(off.state.view(field), on.state.view(field))


def test_no_new_compiles_under_tracing():
    """Fully instrumented re-runs reuse every warmed compiled program: the
    jit-cache size is flat and the obs compile counter agrees with it."""
    st.stream_profile_population(FLEET, chunk_size=CHUNK)        # warm
    n_cache = len(substrate._CHUNK_JIT_CACHE)
    compiles = lambda: obs.REGISTRY.value(
        "repro_compile_programs_total", cache="chunk",
        entry="stream_profile")
    c0 = compiles()
    with tempfile.TemporaryDirectory() as tmp, jax.profiler.trace(tmp):
        st.stream_profile_population(FLEET, chunk_size=CHUNK)
    assert len(substrate._CHUNK_JIT_CACHE) == n_cache
    assert compiles() == c0
    # and the reuse counter DID move: the cache was hit, not bypassed
    assert obs.REGISTRY.value("repro_compile_reuse_total", cache="chunk",
                              entry="stream_profile") > 0


# --------------------------------------------------------- memsim compat shim

def test_memsim_compat_shim():
    from repro.core import ramlite
    from repro.memsim import sim
    assert isinstance(sim.N_TRACES, int)
    assert sim.N_TRACES == obs.REGISTRY.value("repro_memsim_traces_total")
    assert sim.N_TRACE_BUILDS == obs.REGISTRY.value(
        "repro_memsim_trace_builds_total")
    assert ramlite.N_TRACES == sim.N_TRACES       # facade chains the shim
    with pytest.raises(AttributeError):
        sim.N_NOT_A_COUNTER


# ------------------------------------------------------- serve-layer metrics

def test_fleet_server_metrics_consistency():
    server = FleetServer(FLEET, FleetConfig(chunk_size=CHUNK))
    stats = server.ingest(now=0.0)
    server.query(0)                                   # serials are 0..D-1
    server.query_batch(np.asarray([1, 3, 3, 7]))
    met = server.metrics()
    assert met["paths"] == {"hit": stats["hits"],
                            "discover": stats["misses"],
                            "conventional": stats["conventional"]}
    assert met["ingested"] == D
    assert met["queries"] == 5                        # 1 + a batch of 4
    assert met["query_latency_seconds"]["count"] == 2  # one span per call
    assert met["hit_rate"] == pytest.approx(stats["hits"] / D)
    assert met["generations"] == stats["n_generations"]
    assert met["max_table_age_years"] == pytest.approx(
        server.staleness()["max_staleness_years"])
    # two servers do not share series: a fresh one starts at zero
    fresh = FleetServer(FLEET, FleetConfig(chunk_size=CHUNK))
    met2 = fresh.metrics()
    assert met2["queries"] == 0
    assert met2["paths"] == {"hit": 0, "discover": 0, "conventional": 0}
    assert met2["server"] != met["server"]
