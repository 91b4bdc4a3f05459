"""The configurations pin the program's input generators."""
import copy
import json

import numpy as np
import pytest

import fleetcell
import harness


def _config(name):
    bench = harness.load_benchmark()
    conf = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((harness.ROOT / conf["file"]).read_text())


def test_fleet_pin_matches_the_program():
    fleetcell.check_pins(_config("ddr3-fleet-paper"))


def test_fleet_pin_mismatch_names_what_changed():
    conf = copy.deepcopy(_config("ddr3-fleet-paper"))
    conf["pins"]["fleet"]["sha256"] = "0" * 64
    with pytest.raises(harness.BenchError, match="synthetic_fleet changed"):
        fleetcell.check_pins(conf)
    conf = copy.deepcopy(_config("ddr3-fleet-paper"))
    conf["designs"] = conf["designs"][::-1]
    with pytest.raises(harness.BenchError, match="fleet_templates changed"):
        fleetcell.check_pins(conf)
    conf = copy.deepcopy(_config("ddr3-fleet-paper"))
    conf["fleet_config"]["threshold"] = 0.5
    with pytest.raises(harness.BenchError, match="threshold"):
        fleetcell.check_pins(conf)


def test_arrivals_bring_only_the_traffics_designs():
    conf = _config("ddr3-fleet-paper")
    tr = json.loads((harness.BENCH / "traffic" /
                     "ingest-strong-die-mix.json").read_text())
    order = fleetcell.arrivals(conf, tr, 1000)
    assert len(order) == 1000 and (np.diff(order) > 0).all()
    names = np.asarray(conf["designs"])[order % len(conf["designs"])]
    assert set(names) == set(tr["designs"])
    assert not {"B-F", "B-M", "C-F"} & set(names)
    # each chunk of 64 in serial order holds 8 DIMMs of each design
    _, counts = np.unique(names[:64], return_counts=True)
    assert counts.tolist() == [8] * 8
