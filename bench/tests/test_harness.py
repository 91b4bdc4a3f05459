"""Finding cells by name, the device table, the input pins, and the
refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT


def test_every_cell_resolves_to_files_and_metrics():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        spec = harness.resolve_cell(cell["name"], bench)
        harness.loop(spec["traffic"])
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        for m in spec["per_layer"]:
            assert harness.metric_reader(m["name"]).read
            assert any(e["name"] == m["moves"] for e in spec["end_to_end"])


def test_additions_need_no_edit_of_an_existing_file(tmp_path):
    """A new configuration, traffic mix, metric and cell are new files plus
    new entries in BENCHMARK.json."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = harness.load_benchmark()
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "ddr3-fleet-paper.json").read_text())
    conf["name"] = "ddr3-fleet-new"
    (b / "configs" / "ddr3-fleet-new.json").write_text(json.dumps(conf))
    (b / "traffic" / "ingest-new-mix.json").write_text(json.dumps(
        {"loop": "ingest", "designs": ["A-A"], "arrivals": 128,
         "dimms_per_call": 64, "now_years": 1.0, "founding_max_chunks": 2,
         "warm_every_row_counts": [], "check": {"mismatch_share_limit": 0}}))
    (b / "metrics" / "dimms.new.py").write_text(
        "def read(ctx):\n    return float(ctx.counters['chunks'])\n")
    bench["configs"].append({"name": "ddr3-fleet-new", "source": "x",
                             "file": "bench/configs/ddr3-fleet-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "ingest.new", "config":
                               "ddr3-fleet-new", "traffic": "ingest-new-mix",
                               "chips": 1, "why": "x"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "ingest_dimms_per_s")["workloads"].append(
             "ingest.new")
    bench["per_layer"].append({"name": "dimms.new", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "Service",
                               "moves": "ingest_dimms_per_s",
                               "workloads": ["ingest.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.resolve_cell("ingest.new", harness.load_benchmark(tmp_path),
                                tmp_path)
    assert spec["config"]["name"] == "ddr3-fleet-new"
    assert spec["traffic"]["now_years"] == 1.0
    assert harness.loop(spec["traffic"], tmp_path).END_TO_END \
        == "ingest_dimms_per_s"
    names = [m["name"] for m in spec["per_layer"]]
    assert names == ["dimms.new"]
    reader = harness.metric_reader("dimms.new", tmp_path)
    assert reader.read(type("C", (), {"counters": {"chunks": 3}})) == 3.0


def test_device_peaks_know_the_v5e_and_refuse_other_kinds():
    row = harness.device_peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
    with pytest.raises(harness.BenchError):
        harness.device_peaks("TPU v9 imaginary")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "ingest.strong-die-mix", "--seed", "4294967301",
                           "--seconds", "1", "--trace", "0"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_a_cpu_host_exits_nonzero_with_no_result():
    out = _run(ROOT, {})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_a_backend_override_is_refused():
    out = _run(ROOT, {"REPRO_FORCE_REF": "1"})
    assert out.returncode != 0 and not out.stdout.strip()


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path, {})
    assert out.returncode != 0 and not out.stdout.strip()
