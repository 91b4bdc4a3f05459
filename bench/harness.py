"""What every cell of the benchmark shares: finding its files by name, the
compile watch, the device peaks and the input digests.

``BENCHMARK.json`` names every cell, configuration and metric.  A cell's
configuration is ``configs[].file``; its traffic mix is
``bench/traffic/<traffic>.json``, whose ``loop`` names the general
loop under ``bench/loops/`` that runs it; a per-layer metric ``<m>`` is
read by ``bench/metrics/<m>.py``.  Adding a cell, a configuration, a mix of
an existing loop or a metric is adding files and entries.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """A run that cannot be measured: it prints no result line."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no {path.name} in {root}")
    return json.loads(path.read_text())


def judge(checks: dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"BENCHMARK.json names no {what} {name!r}")


def resolve_cell(name: str, bench: dict, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic mix and metric names."""
    cell = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], cell["config"], "config")
    config = json.loads((root / conf["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.exists():
        raise BenchError(f"no traffic file {traffic_file.relative_to(root)}")
    traffic = json.loads(traffic_file.read_text())

    def listed(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
            "per_layer": [m for m in bench["per_layer"] if listed(m)]}


def load_module(path: Path):
    """Import a benchmark file by path (metric readers and loops are
    found by the names in ``BENCHMARK.json``, not imported by name)."""
    if not path.exists():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop(traffic: dict, root: Path = ROOT):
    return load_module(root / "bench" / "loops" / f"{traffic['loop']}.py")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def device_peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip; an unknown ``device_kind`` is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    for row in table["devices"]:
        if row["device_kind"] == kind:
            return row
    raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")


def sha256_arrays(arrays: dict) -> str:
    """Digest of named arrays: name, dtype, shape and bytes of each, in
    name order."""
    import numpy as np
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(f"{k}|{a.dtype.str}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


class CompileWatch:
    """Counts backend compiles and persistent-cache hits through
    ``jax.monitoring``; ``mark()`` starts a new phase."""

    def __init__(self):
        import jax
        self.phases: list[dict] = []
        self.mark("setup")
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def mark(self, phase: str) -> None:
        self.phases.append({"phase": phase, "compiles": 0,
                            "compile_s": 0.0, "cache_hits": 0})

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.phases[-1]["compiles"] += 1
            self.phases[-1]["compile_s"] += duration

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.phases[-1]["cache_hits"] += 1

    def phase(self, name: str) -> dict:
        return next(p for p in self.phases if p["phase"] == name)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
