"""CPU tests of the benchmark (outside the repository's tier-1
``testpaths``): ``python -m pytest bench/tests``."""
import copy
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {"rows_per_mat": 64, "cols_per_mat": 64, "mats_x": 4,
        "subarrays": 2, "banks": 1, "chips": 8, "burst_bits": 64,
        "open_bitline": True}
SMALL = dict(TINY, rows_per_mat=128, cols_per_mat=128, mats_x=8,
             subarrays=4)


def shrink(geometry=TINY, chunk=16, **traffic):
    """A ``patch`` for ``run.run_cell`` that runs a cell at a size a CPU
    test holds: the configuration's geometry and chunk, and any traffic
    keys given (``check`` merges)."""
    def patch(run):
        run.config = copy.deepcopy(run.config)
        run.traffic = copy.deepcopy(run.traffic)
        run.config["geometry"] = dict(geometry)
        run.config["fleet_config"]["chunk_size"] = chunk
        tr = run.traffic
        if "dimms_per_call" in tr:
            tr["dimms_per_call"] = chunk
        if tr["loop"] == "ingest":
            tr["warm_every_row_counts"] = []
        tr.update({k: v for k, v in traffic.items() if k != "check"})
        tr["check"].update(traffic.get("check", {}))
    return patch


@pytest.fixture
def run_cell():
    import run as bench_run

    def go(workload, patch, seed=2 ** 31 + 7, seconds=2.0, control=None):
        return bench_run.run_cell(workload, seed, seconds, False,
                                  require_chip=False, check_pins=False,
                                  patch=patch, control=control)
    return go
