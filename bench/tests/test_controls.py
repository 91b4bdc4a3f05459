"""Each cell's control comes out not correct: the plain reference computed
in bfloat16, one precision below the configuration's float32, put in the
program's place.  The fleet runs at SMALL geometry (at TINY the 2.5 ns
grid hides bfloat16); the chip readings at full size are in PERF.md."""
from conftest import SMALL, shrink

import pytest

FLEET = shrink(SMALL, 64)


@pytest.mark.parametrize("cell",
                         ["ingest.strong-die-mix", "tick.strong-die-mix"])
def test_fleet_control_is_not_correct(run_cell, cell):
    sound = run_cell(cell, FLEET, seconds=1.0)
    assert sound["correct"], sound["checks"]
    ctl = run_cell(cell, FLEET, seconds=1.0, control="bfloat16")
    share = ctl["checks"]["table_mismatch_share"]
    assert not ctl["correct"] and share["value"] > share["limit"]
