"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (the derived column is a compact
key=value report of the figure's quantities vs the paper's claims).

    PYTHONPATH=src python -m benchmarks.run [--only fig18] [--check]

``--check`` validates every emitted row against the CSV schema AND every
committed ``benchmarks/BENCH_*.json`` trajectory file against the bench
entry schema, exiting nonzero on any violation — the CI guard that keeps
downstream scrapers (EXPERIMENTS.md tooling, dashboards) from silently
ingesting a broken figure row or a hand-mangled bench trajectory.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

_NAME_RE = re.compile(r"^[A-Za-z0-9_.]+$")


def validate_row(line: str) -> str | None:
    """CSV schema check for one emitted row; returns an error string or None.

    Schema: ``name,us_per_call,derived`` — a word-safe name, a nonnegative
    numeric wall time, and a non-empty derived blob whose first ';'-segment
    is a key=value pair (later segments may be free text: some figures quote
    the paper's claim verbatim, semicolons included).
    """
    parts = line.split(",", 2)
    if len(parts) != 3:
        return f"expected 3 comma fields, got {len(parts)}: {line!r}"
    name, wall, derived = parts
    if not _NAME_RE.match(name):
        return f"malformed name field: {name!r}"
    try:
        if float(wall) < 0:
            return f"negative wall time: {wall!r}"
    except ValueError:
        return f"non-numeric wall time: {wall!r}"
    if not derived:
        return f"empty derived field: {line!r}"
    if "=" not in derived.split(";", 1)[0]:
        return f"derived field without key=value lead: {derived!r}"
    if name.startswith("kernel_"):
        # kernel rows must say which backend actually ran them — a real
        # ``backend=<platform>-<mode>`` tag, not the legacy hardcoded
        # ``interpret-mode`` literal (which lied in the oracle CI leg)
        m = re.search(r"(?:^|;)backend=([^;]*)", derived)
        if not m:
            return f"kernel row without backend= column: {line!r}"
        tag = m.group(1)
        if tag == "interpret-mode" \
                or not re.fullmatch(r"[a-z0-9]+(-[a-z0-9]+)*", tag):
            return f"kernel row with legacy/malformed backend {tag!r}: {line!r}"
    return None


# Required keys of every BENCH_*.json trajectory entry, with accepted JSON
# types.  Optional per-bench keys (discovery counters, the operating-point
# sweep block, ...) are allowed on top; the required core is what every
# appender writes and what the dashboards key on.
_BENCH_SCHEMA: dict[str, type | tuple] = {
    "date": str, "backend": str, "geometry": str, "n_dimms": int,
    "chunk_size": int, "n_chunks": int, "profile_s": (int, float),
    "budget_mb": int, "peak_rss_mb": (int, float), "prefix_parity": bool,
}
_BENCH_DATE_RE = re.compile(r"^\d{4}-\d{2}-\d{2}$")
_BENCH_BACKEND_RE = re.compile(r"^[a-z0-9]+(-[a-z0-9]+)*$")

# Per-file extensions of the required core: the serving-layer trajectory
# additionally commits its gate results, and a committed entry must have
# PASSED the gates (a false here means someone committed a failing run).
_BENCH_FILE_SCHEMAS: dict[str, dict[str, type | tuple]] = {
    "BENCH_serve.json": {
        "queries_per_s": (int, float), "hits": int, "misses": int,
        "conventional": int, "n_generations": int,
        "staleness_bound_years": (int, float),
        "max_staleness_years": (int, float), "staleness_bounded": bool,
        "ckpt_roundtrip_ok": bool,
    },
}
_BENCH_TRUE_KEYS: dict[str, tuple] = {
    "BENCH_serve.json": ("staleness_bounded", "ckpt_roundtrip_ok",
                         "prefix_parity"),
}

# The obs-registry block serve_bench.py embeds in new BENCH_serve.json
# entries.  OPTIONAL per entry (trajectory entries predate the obs layer),
# but when present it must be complete, well-typed, and self-declared
# consistent — ``consistent`` is the bench's cross-check that the registry
# agreed with every independently computed gate value.
_METRICS_SCHEMA: dict[str, type | tuple] = {
    "paths": dict, "hit_rate": (int, float), "queries": int,
    "query_latency_p50_us": (int, float),
    "query_latency_p99_us": (int, float),
    "max_table_age_years": (int, float), "reprofiled": int,
    "chunk_compiles": dict, "consistent": bool,
}
_METRICS_PATHS = frozenset({"hit", "discover", "conventional"})


def validate_metrics_block(entry: dict, where: str) -> list[str]:
    """Schema check for the optional ``metrics`` block of a serve entry."""
    if "metrics" not in entry:
        return []
    met = entry["metrics"]
    if not isinstance(met, dict):
        return [f"{where}: metrics block is not a JSON object"]
    errs = []
    for key, typ in _METRICS_SCHEMA.items():
        if key not in met:
            errs.append(f"{where}: metrics block missing key {key!r}")
        elif isinstance(met[key], bool) and typ is not bool:
            errs.append(f"{where}: metrics.{key}={met[key]!r} must be "
                        f"{typ}, got bool")
        elif not isinstance(met[key], typ):
            errs.append(f"{where}: metrics.{key}={met[key]!r} is not {typ}")
    if errs:
        return errs
    if set(met["paths"]) != _METRICS_PATHS:
        errs.append(f"{where}: metrics.paths keys {sorted(met['paths'])} != "
                    f"{sorted(_METRICS_PATHS)}")
    for block in ("paths", "chunk_compiles"):
        for k, v in met[block].items():
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{where}: metrics.{block}[{k!r}]={v!r} must be "
                            "a nonnegative int")
    if not 0.0 <= met["hit_rate"] <= 1.0:
        errs.append(f"{where}: metrics.hit_rate={met['hit_rate']} "
                    "outside [0, 1]")
    if met["consistent"] is not True:
        errs.append(f"{where}: metrics.consistent={met['consistent']!r} — "
                    "only registry-consistent runs may be committed")
    return errs


def validate_bench_entry(entry, where: str, *,
                         extra_schema: dict | None = None,
                         true_keys: tuple = ()) -> list[str]:
    """Schema check for one BENCH trajectory entry; returns error strings."""
    if not isinstance(entry, dict):
        return [f"{where}: entry is not a JSON object"]
    errs = []
    schema = dict(_BENCH_SCHEMA, **(extra_schema or {}))
    for key, typ in schema.items():
        if key not in entry:
            errs.append(f"{where}: missing required key {key!r}")
            continue
        val = entry[key]
        # bool is an int subclass in Python; a true/false n_dimms is malformed
        if isinstance(val, bool) and typ is not bool:
            errs.append(f"{where}: {key}={val!r} must be {typ}, got bool")
        elif not isinstance(val, typ):
            errs.append(f"{where}: {key}={val!r} is not {typ}")
    if errs:
        return errs
    if not _BENCH_DATE_RE.match(entry["date"]):
        errs.append(f"{where}: malformed date {entry['date']!r}")
    if not _BENCH_BACKEND_RE.match(entry["backend"]):
        errs.append(f"{where}: malformed backend tag {entry['backend']!r} "
                    "(want <platform>-<mode>, e.g. cpu-pallas-interpret)")
    for key in ("n_dimms", "chunk_size", "n_chunks"):
        if entry[key] <= 0:
            errs.append(f"{where}: {key}={entry[key]} must be positive")
    for key in ("profile_s", "peak_rss_mb"):
        if entry[key] < 0:
            errs.append(f"{where}: negative {key}={entry[key]}")
    for key in true_keys:
        if entry.get(key) is not True:
            errs.append(f"{where}: gate {key}={entry.get(key)!r} — only "
                        "passing runs may be committed")
    return errs


# The per-backend kernel trajectory (BENCH_kernels.json) has its own row
# shape — one (kernel, backend) timing per entry, not the streaming-bench
# core — so it gets a dedicated validator instead of _BENCH_SCHEMA.
_KERNEL_BENCH_SCHEMA: dict[str, type | tuple] = {
    "date": str, "backend": str, "kernel": str, "shape": str,
    "us_per_call": (int, float), "speedup_vs_ref": (int, float),
}
_KERNEL_SHAPE_RE = re.compile(r"^[A-Za-z0-9_x]+$")


def validate_kernel_bench_entries(history: list, name: str) -> list[str]:
    """Schema check for the whole BENCH_kernels.json trajectory: every row
    well-typed, every kernel a registry dispatch site, and every backend
    that appears covering ALL dispatch sites — a partial backend sweep is a
    broken trajectory (a dashboard would silently plot holes)."""
    from repro.kernels.registry import KERNEL_NAMES
    known = set(KERNEL_NAMES)
    errs: list[str] = []
    per_backend: dict[str, set] = {}
    for i, entry in enumerate(history):
        where = f"{name}[{i}]"
        if not isinstance(entry, dict):
            errs.append(f"{where}: entry is not a JSON object")
            continue
        bad = False
        for key, typ in _KERNEL_BENCH_SCHEMA.items():
            if key not in entry:
                errs.append(f"{where}: missing required key {key!r}")
                bad = True
            elif isinstance(entry[key], bool) or not isinstance(entry[key],
                                                               typ):
                errs.append(f"{where}: {key}={entry[key]!r} is not {typ}")
                bad = True
        if bad:
            continue
        if not _BENCH_DATE_RE.match(entry["date"]):
            errs.append(f"{where}: malformed date {entry['date']!r}")
        if not _BENCH_BACKEND_RE.match(entry["backend"]):
            errs.append(f"{where}: malformed backend {entry['backend']!r}")
        if entry["kernel"] not in known:
            errs.append(f"{where}: unknown kernel {entry['kernel']!r} "
                        f"(registry sites: {sorted(known)})")
        if not _KERNEL_SHAPE_RE.match(entry["shape"]):
            errs.append(f"{where}: malformed shape {entry['shape']!r}")
        if entry["us_per_call"] <= 0:
            errs.append(f"{where}: us_per_call={entry['us_per_call']} "
                        "must be positive")
        if entry["speedup_vs_ref"] <= 0:
            errs.append(f"{where}: speedup_vs_ref="
                        f"{entry['speedup_vs_ref']} must be positive")
        per_backend.setdefault(entry["backend"], set()).add(entry["kernel"])
    for tag, kernels_seen in sorted(per_backend.items()):
        missing = known - kernels_seen
        if missing:
            errs.append(f"{name}: backend {tag!r} missing kernels "
                        f"{sorted(missing)} — every backend row set must "
                        "cover all registry dispatch sites")
    return errs


def check_bench_files(bench_dir: Path) -> list[str]:
    """Validate every committed ``BENCH_*.json`` under ``bench_dir``.

    Zero matching files is itself an error — the committed trajectory exists,
    so an empty glob means the check is looking in the wrong place."""
    files = sorted(bench_dir.glob("BENCH_*.json"))
    if not files:
        return [f"no BENCH_*.json files under {bench_dir}"]
    errs = []
    for path in files:
        try:
            history = json.loads(path.read_text())
        except ValueError as e:
            errs.append(f"{path.name}: invalid JSON: {e}")
            continue
        if not isinstance(history, list) or not history:
            errs.append(f"{path.name}: trajectory must be a non-empty list")
            continue
        if path.name == "BENCH_kernels.json":
            errs.extend(validate_kernel_bench_entries(history, path.name))
            continue
        for i, entry in enumerate(history):
            where = f"{path.name}[{i}]"
            errs.extend(validate_bench_entry(
                entry, where,
                extra_schema=_BENCH_FILE_SCHEMAS.get(path.name),
                true_keys=_BENCH_TRUE_KEYS.get(path.name, ())))
            if path.name == "BENCH_serve.json" and isinstance(entry, dict):
                errs.extend(validate_metrics_block(entry, where))
    return errs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="validate the CSV schema of every emitted row; "
                         "exit nonzero on a malformed one")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    failures = []

    def emit(line: str) -> None:
        if args.check:
            err = validate_row(line)
            if err:
                failures.append(err)
                print(f"MALFORMED ROW: {err}", file=sys.stderr)
        print(line, flush=True)

    from benchmarks.paper_figures import FIGURES

    print("name,us_per_call,derived")
    for name, fn in FIGURES.items():
        if args.only and args.only not in name:
            continue
        derived, wall = fn()
        blob = ";".join(f"{k}={v}" for k, v in derived.items())
        emit(f"{name},{wall * 1e6:.0f},{blob}")

    if not args.skip_kernels and (not args.only or "kernel" in args.only):
        from benchmarks.kernel_bench import backend_tag, kernels
        tag = backend_tag()
        for k, v in kernels().items():
            emit(f"kernel_{k},{v},backend={tag}")

    if args.check:
        bench_errs = check_bench_files(Path(__file__).parent)
        for err in bench_errs:
            print(f"MALFORMED BENCH ENTRY: {err}", file=sys.stderr)
        failures.extend(bench_errs)
        if failures:
            sys.exit(f"--check: {len(failures)} schema violation(s)")
        print("--check: all rows conform to name,us_per_call,derived and "
              "all BENCH_*.json trajectories conform to the bench schema",
              file=sys.stderr)


if __name__ == "__main__":
    main()
