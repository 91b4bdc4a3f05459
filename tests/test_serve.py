"""Fleet serving layer: paths, oracle parity, staleness, checkpointing.

The server's contract has four legs (mirrored by benchmarks/serve_bench.py):
every ingested DIMM gets a table by the cheapest trusted path; every served
table is bit-identical to the dense oracle for its path; the re-profiling
queue keeps table age under the fleet's staleness bound; and a checkpoint
roundtrip — including one taken MID-INGEST — reproduces the serving state
exactly, labels and deadlines included.
"""
import dataclasses
import heapq
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import repro.serve.server as server_mod
from repro.checkpoint import CheckpointManager
from repro.core.geometry import TINY
from repro.core.population import synthetic_fleet
from repro.core.substrate import profile_population_arrays
from repro.obs import REGISTRY
from repro.serve import (PATH_CONVENTIONAL, PATH_DISCOVER, PATH_HIT,
                         FleetConfig, FleetServer, oracle_parity)
from repro.serve.state import FleetState, GenerationCache

N, CHUNK = 128, 64


@pytest.fixture(scope="module")
def fleet():
    return synthetic_fleet(N, TINY, seed=0)


@pytest.fixture(scope="module")
def served(fleet):
    """One fully-ingested server at fleet age 0 (tests must not mutate it —
    mutation tests build their own servers)."""
    server = FleetServer(fleet, FleetConfig(chunk_size=CHUNK))
    stats = server.ingest(now=0.0)
    return server, stats


def _oracle(batch, region, cfg, age):
    aged = dataclasses.replace(
        batch, age_years=np.full(batch.n_dimms, np.float32(age)))
    return np.asarray(profile_population_arrays(
        aged, region=region, temp_C=cfg.profile_temp_C,
        refresh_ms=cfg.profile_refresh_ms, guard_cycles=cfg.guard_cycles,
        multibit_only=cfg.multibit_only), np.float32)[:, :4]


# --------------------------------------------------------------- ingest paths

def test_ingest_path_accounting(served):
    server, stats = served
    assert stats["ingested"] == N
    assert stats["hits"] + stats["misses"] + stats["conventional"] == N
    # the seed-0 TINY fleet exercises all three paths
    assert stats["hits"] > 0 and stats["misses"] > 0
    assert stats["conventional"] > 0
    assert stats["n_generations"] > 0
    path = server.state.view("path")
    assert int((path == PATH_HIT).sum()) == stats["hits"]
    assert int((path == PATH_DISCOVER).sum()) == stats["misses"]
    assert int((path == PATH_CONVENTIONAL).sum()) == stats["conventional"]


def test_unverified_generations_route_conventional(served):
    """Founding verification is the trust gate: a generation whose vote pool
    was too small or too incoherent keeps its label (cluster accounting)
    but every member — founders included — takes the conventional sweep."""
    server, _ = served
    labels = server.state.view("label")
    path = server.state.view("path")
    assert server.founding_stats, "ingest must found at least one generation"
    for gen, st in server.founding_stats.items():
        assert st["verified"] == server.cache.verified(gen)
        members = path[labels == gen]
        if st["verified"]:
            assert st["n_founders"] >= server.cfg.min_founders
            assert st["share_mean"] >= server.cfg.consensus_min_share
            assert (members != PATH_CONVENTIONAL).all()
            assert len(server.cache.ext_rows(gen)) == server.cfg.k_rows
        else:
            assert (members == PATH_CONVENTIONAL).all()
    # signatureless DIMMs (label -1) are always conventional
    assert (path[labels < 0] == PATH_CONVENTIONAL).all()


def test_served_tables_bit_identical_to_oracle(served, fleet):
    """Hit/discover tables must equal the geometry-oracle diva_profile sweep
    (region="worst"), conventional tables the every-row sweep — bit for bit,
    at the oracle's own operating point (multibit_only included)."""
    server, _ = served
    batch = fleet.chunk(0, N)
    diva = _oracle(batch, "worst", server.cfg, age=0.0)
    conv = _oracle(batch, "all", server.cfg, age=0.0)
    is_conv = server.state.view("path") == PATH_CONVENTIONAL
    oracle = np.where(is_conv[:, None], conv, diva)
    np.testing.assert_array_equal(server.state.view("table"), oracle)


def _bench_reference():
    """The benchmark's plain reference of a served table
    (``bench/reference/fleet.py``, which imports nothing of the program)."""
    path = (Path(__file__).resolve().parents[1] / "bench" / "reference"
            / "fleet.py")
    spec = importlib.util.spec_from_file_location("bench_reference_fleet",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_design_served_the_plain_reference_table(served, fleet):
    """All 11 designs arrive, the three low-variation dies (B-F, B-M, C-F)
    among them, at TINY, the smallest geometry whose fleet founds
    generations: every served table equals the plain reference's on the
    path the server reports, its design-worst rows {0, R-1} or every row.
    Tolerance 0: a table is a point of the 2.5 ns test grid plus one guard
    cycle, and both sides make the same counter-hash draws, so they agree
    exactly or differ by a whole grid step."""
    server, _ = served
    ref = _bench_reference()
    batch = fleet.chunk(0, N)
    serials = np.asarray(batch.serial)
    assert len(np.unique(serials % 11)) == 11
    lv = {k: np.asarray(getattr(batch, k)) for k in ref.LEAVES}
    cfg, R = server.cfg, TINY.rows_per_mat
    kw = dict(age_years=0.0, temp_C=cfg.profile_temp_C,
              refresh_ms=cfg.profile_refresh_ms,
              guard_cycles=cfg.guard_cycles, multibit=cfg.multibit_only,
              cols=TINY.cols_per_mat, mats=TINY.mats_x, chips=TINY.chips,
              block=N)
    worst = ref.tables(lv, ref.design_worst_rows(R), **kw)
    every = ref.tables(lv, np.arange(R), **kw)
    rows = server.state.rows_for(serials)
    is_conv = server.state.view("path")[rows] == PATH_CONVENTIONAL
    np.testing.assert_array_equal(server.state.view("table")[rows],
                                  np.where(is_conv[:, None], every, worst))


def _found_counts(server) -> dict:
    fam = REGISTRY.get("repro_serve_found_total")
    return {(v, r): fam.labels(server=server._sid, verified=v,
                               region=r).value()
            for v in ("true", "false") for r in ("both_arms", "one_arm")}


def test_found_counter_moves_at_each_founding(served):
    """``repro_serve_found_total{verified, region}`` counts every founding
    by its outcome, and a one-arm founding is never verified."""
    server, _ = served
    tally = {k: 0 for k in _found_counts(server)}
    for st in server.founding_stats.values():
        tally[str(st["verified"]).lower(), st["region"]] += 1
        assert st["region"] == "both_arms" or not st["verified"]
    assert _found_counts(server) == tally
    assert sum(tally.values()) == server.cache.n_generations > 0


def test_one_arm_founding_is_never_verified(fleet, monkeypatch):
    """Where the founding profile shows one arm of the V, the generation is
    installed unverified whatever its vote, and its members, founders
    included, take the every-row sweep."""
    monkeypatch.setattr(server_mod, "covers_both_arms", lambda p, rows: False)
    server = FleetServer(fleet, FleetConfig(chunk_size=CHUNK))
    stats = server.ingest(CHUNK, now=0.0)
    n = stats["n_generations"]
    assert n > 0 and stats["conventional"] == CHUNK
    assert not any(server.cache.verified(g) for g in range(n))
    counts = _found_counts(server)
    assert counts.pop(("false", "one_arm")) == n
    assert set(counts.values()) == {0}


# ------------------------------------------------------------------- queries

def test_query_and_query_batch(served):
    server, _ = served
    rec = server.query(7)
    i = server.state.index[7]
    np.testing.assert_array_equal(rec["table"], server.state.view("table")[i])
    assert rec["path"] in (PATH_HIT, PATH_DISCOVER, PATH_CONVENTIONAL)
    assert rec["due_at"] == pytest.approx(rec["profiled_at"]
                                          + server.state.view("horizon")[i])
    serials = np.asarray([3, 90, 3, 41])          # duplicates allowed
    tab = server.query_batch(serials)
    assert tab.shape == (4, 4)
    rows = server.state.rows_for(serials)
    np.testing.assert_array_equal(tab, server.state.view("table")[rows])
    with pytest.raises(KeyError):
        server.query(N + 17)


def test_duplicate_serial_rejected():
    st = FleetState()
    args = (np.zeros((1, 4), np.float32), [0], [0], [0.0], [1.0], [1.0])
    st.append([5], *args)
    with pytest.raises(ValueError, match="already registered"):
        st.append([5], *args)


# ----------------------------------------------------------------- staleness

def test_staleness_queue_ordering(served):
    """The deadline heap drains in due_at order, covers the whole fleet,
    and its minimum matches the state's earliest deadline."""
    server, _ = served
    heap = list(server._heap)
    assert len(heap) == N
    assert heap[0][0] == pytest.approx(float(server.state.view("due_at").min()))
    drained = []
    while heap:
        drained.append(heapq.heappop(heap)[0])
    assert drained == sorted(drained)
    rep = server.staleness()
    assert rep["max_staleness_years"] == 0.0      # just profiled
    assert rep["n_overdue"] == 0
    assert rep["bound_years"] == pytest.approx(
        float(server.state.view("horizon").max()))
    # nothing is due at age 0: a tick is a no-op (fixture stays pristine)
    assert server.tick(0.0)["reprofiled"] == 0


def test_tick_reprofiles_due_dimms_to_aged_oracle():
    """Aging past the horizon re-profiles due DIMMs at their cached regions
    under the aged condition — bit-identical to the dense oracle at that
    age — and re-arms their deadlines so staleness stays bounded."""
    n = 64
    fleet = synthetic_fleet(n, TINY, seed=0)
    server = FleetServer(fleet, FleetConfig(chunk_size=n))
    server.ingest(now=0.0)
    bound = server.staleness()["bound_years"]
    now = 3.0
    assert now > float(server.state.view("horizon").min())
    was_due = server.state.view("due_at").copy() <= now
    tick = server.tick(now)
    assert tick["reprofiled"] == int(was_due.sum()) > 0
    prof = server.state.view("profiled_at")
    np.testing.assert_array_equal(prof[was_due], np.float32(now))
    np.testing.assert_array_equal(prof[~was_due], np.float32(0.0))
    np.testing.assert_allclose(
        server.state.view("due_at")[was_due],
        now + server.state.view("horizon")[was_due])
    rep = server.staleness(now)
    assert rep["max_staleness_years"] <= bound + 1e-6
    assert rep["n_overdue"] == 0
    # re-profiled tables == dense aged oracle for each path
    batch = fleet.chunk(0, n)
    diva = _oracle(batch, "worst", server.cfg, age=now)
    conv = _oracle(batch, "all", server.cfg, age=now)
    is_conv = server.state.view("path") == PATH_CONVENTIONAL
    oracle = np.where(is_conv[:, None], conv, diva)
    np.testing.assert_array_equal(server.state.view("table")[was_due],
                                  oracle[was_due])
    # the library check ages each DIMM to its own profiled_at: the mixed
    # fleet (re-profiled at `now`, the rest at 0) is exact as a whole
    assert oracle_parity(server, n) == {"n_prefix": n, "n_mismatch": 0,
                                        "parity": True}


def test_launch_serve_fleet_takes_geometry_by_name():
    """``launch/serve.py --fleet --geometry NAME`` serves the named preset;
    TINY is the CI default."""
    from repro.launch.serve import serve_fleet
    stats = serve_fleet(64, 32, geometry="TINY")
    assert stats["ingested"] == 64
    assert stats["hits"] + stats["misses"] + stats["conventional"] == 64


# -------------------------------------------------------------- checkpointing

def test_checkpoint_mid_ingest_resume(served, fleet, tmp_path):
    """Save after half the fleet, restore into a fresh server, ingest the
    rest: labels, tables, counters, and deadlines must match the
    single-shot server exactly (the restart-mid-ingest contract)."""
    half = FleetServer(fleet, FleetConfig(chunk_size=CHUNK),
                       checkpoint_dir=str(tmp_path))
    half.ingest(CHUNK, now=0.0)
    assert half._ingested == CHUNK
    half.save(step=0)

    resumed = FleetServer(fleet, FleetConfig(chunk_size=CHUNK),
                          checkpoint_dir=str(tmp_path))
    info = resumed.load()
    assert info["step"] == 0
    assert resumed._ingested == CHUNK
    assert len(resumed.state) == CHUNK
    resumed.ingest(now=0.0)

    single_shot, _ = served
    a, b = single_shot.state_dict(), resumed.state_dict()
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # the restored cosine cache reproduces the exact label sequence
    np.testing.assert_array_equal(single_shot.state.view("label"),
                                  resumed.state.view("label"))


def test_save_requires_checkpoint_dir(fleet):
    server = FleetServer(fleet, FleetConfig(chunk_size=CHUNK))
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        server.save(step=0)
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        server.load()


def test_generation_cache_state_roundtrip():
    cache = GenerationCache(threshold=0.85)
    feats = np.eye(3)                              # three orthogonal leaders
    labels = cache.match(feats)
    assert sorted(labels.tolist()) == [0, 1, 2]
    cache.install(0, [5, 9], verified=True)
    cache.install(1, [2], verified=False)
    cache.hits, cache.misses, cache.conventional = 7, 3, 11

    fresh = GenerationCache(threshold=0.85)
    fresh.load_state(cache.state_dict())
    assert fresh.n_generations == 3
    assert fresh.verified(0) and not fresh.verified(1)
    assert not fresh.verified(2)
    np.testing.assert_array_equal(fresh.ext_rows(0), [5, 9])
    np.testing.assert_array_equal(fresh.ext_rows(1), [2])
    assert fresh.known(0) and fresh.known(1) and not fresh.known(2)
    assert (fresh.hits, fresh.misses, fresh.conventional) == (7, 3, 11)
    # a restored cache matches the same features to the same labels
    np.testing.assert_array_equal(fresh.match(feats), labels)


def test_crash_mid_save_orphan_sweep(tmp_path):
    """A save killed between mkdir and the atomic rename leaves a
    .tmp_step_* dir behind; nothing publishes it, so the next manager init
    sweeps it and restores from the last PUBLISHED step."""
    state = {"a": np.arange(6, dtype=np.int64)}
    CheckpointManager(str(tmp_path)).save(0, state)
    orphan = tmp_path / ".tmp_step_7"
    orphan.mkdir()
    (orphan / "leaf_0.npy").write_bytes(b"torn write")

    mgr = CheckpointManager(str(tmp_path))
    assert not orphan.exists()
    assert mgr.steps() == [0]
    restored, info = mgr.restore({"a": np.zeros(6, np.int64)})
    assert info["step"] == 0
    np.testing.assert_array_equal(restored["a"], state["a"])


def test_keep_validation_and_gc(tmp_path):
    with pytest.raises(ValueError, match="keep must be >= 1"):
        CheckpointManager(str(tmp_path / "bad"), keep=0)
    mgr = CheckpointManager(str(tmp_path), keep=1)
    state = {"a": np.ones(3, np.float32)}
    mgr.save(0, state)
    mgr.save(1, state)
    assert mgr.steps() == [1]                      # keep=1 retains newest only
