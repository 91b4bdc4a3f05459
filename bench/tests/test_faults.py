"""A run whose timed path is broken underneath comes out not correct, once
for each fault its cell can have: an answer altered where it is produced,
half of the batch left out, a step that leaves its state unchanged.  No
cell spans chips, so none can lose an exchange between them."""
import numpy as np
import pytest

from conftest import shrink

INGEST, TICK = "ingest.strong-die-mix", "tick.strong-die-mix"


@pytest.fixture
def altered_tables(monkeypatch):
    from repro.serve import FleetServer
    for name in ("_profile_rows", "_profile_all_rows"):
        orig = getattr(FleetServer, name)

        def bumped(self, *a, _orig=orig, **kw):
            out = np.array(_orig(self, *a, **kw))
            out[:, 0] = out[:, 0] + 1.25
            return out
        monkeypatch.setattr(FleetServer, name, bumped)


@pytest.mark.parametrize("cell", [INGEST, TICK])
def test_altered_table_is_caught(run_cell, altered_tables, cell):
    res = run_cell(cell, shrink())
    assert not res["correct"]
    assert res["checks"]["table_mismatch_share"]["value"] == 1.0


def test_half_of_a_chunk_left_out_is_caught(run_cell, monkeypatch):
    from repro.core.streaming import slice_batch
    from repro.serve import FleetServer
    orig = FleetServer._ingest_chunk

    def half(self, batch, now):
        return orig(self, slice_batch(batch, 0, batch.n_dimms // 2), now)
    monkeypatch.setattr(FleetServer, "_ingest_chunk", half)
    res = run_cell(INGEST, shrink())
    assert not res["correct"] and res["checks"]["unserved"]["value"] > 0


def test_tick_that_leaves_the_state_unchanged_is_caught(run_cell,
                                                       monkeypatch):
    from repro.serve import FleetServer
    monkeypatch.setattr(FleetServer, "_reprofile",
                        lambda self, serials, now: None)
    res = run_cell(TICK, shrink())
    assert not res["correct"] and res["checks"]["misaged"]["value"] > 0


def test_half_of_a_tick_left_out_is_caught(run_cell, monkeypatch):
    from repro.serve import FleetServer
    orig = FleetServer._reprofile

    def half(self, serials, now):
        return orig(self, serials[:len(serials) // 2], now)
    monkeypatch.setattr(FleetServer, "_reprofile", half)
    res = run_cell(TICK, shrink())
    assert not res["correct"] and res["checks"]["misaged"]["value"] > 0
