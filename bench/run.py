#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name in
``BENCHMARK.json`` (see ``bench/harness.py``).  The run refuses any device
but a TPU reached through the compiled Mosaic route, turns on JAX's
persistent compilation cache, checks the configuration's input pins, sets
up and warms every program the window uses, measures for ``--seconds``
seconds (a closed loop of whole calls: the window ends with the last call
that started inside it), reads the device's peak memory, and then compares
what the window served with the plain reference (``bench/reference/``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.  A run that cannot
be measured (no TPU, too few chips, a moved input pin) exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(1, str(BENCH.parent / "src"))

import harness  # noqa: E402
from harness import BenchError, log  # noqa: E402


def guard(chips: int) -> list:
    """The run's devices; raises unless JAX runs on at least ``chips``
    TPUs through the compiled Mosaic route with no backend override."""
    for var in ("REPRO_FORCE_REF", "REPRO_BACKEND"):
        if os.environ.get(var):
            raise BenchError(f"{var} is set: the benchmark runs the "
                             f"platform's own route")
    import jax
    if jax.default_backend() != "tpu":
        raise BenchError(f"JAX found no TPU (backend "
                         f"{jax.default_backend()!r})")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devices)}")
    from repro.kernels import ops
    if ops.backend_tag() != "tpu-mosaic":
        raise BenchError(f"kernel route is {ops.backend_tag()!r}, not "
                         f"'tpu-mosaic'")
    return devices


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_run(spec: dict, seed: int) -> SimpleNamespace:
    return SimpleNamespace(config=spec["config"], traffic=spec["traffic"],
                           seed=int(seed), notes={}, failed=0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run of one cell; returns the result line's object."""
    return run_cell_state(workload, seed, seconds, trace, **kw)[0]


def run_cell_state(workload: str, seed: int, seconds: float, trace: bool,
                   *, root: Path = harness.ROOT, require_chip: bool = True,
                   check_pins: bool = True, patch=None,
                   t_start: float = T_START, control: str | None = None):
    """One run of one cell: (result, the loop's run state, the loop module).
    Tests call it with ``require_chip=False`` and a ``patch(run)`` that
    shrinks the configuration; ``control`` (a dtype name) puts the
    reference computed in that precision in the program's place for the
    comparison (``bench/control.py``)."""
    bench = harness.load_benchmark(root)
    spec = harness.resolve_cell(workload, bench, root)
    chips = spec["cell"]["chips"]
    import jax
    devices = guard(chips) if require_chip else jax.devices()
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = harness.CompileWatch()
    dev = devices[0]
    if require_chip:
        harness.device_peaks(dev.device_kind, root)
    loop = harness.loop(spec["traffic"], root)
    run = make_run(spec, seed)
    if patch is not None:
        patch(run)
    if check_pins:
        loop.check_pins(run)
    t_setup = time.perf_counter()
    log(f"{workload} seed {seed}: {dev.platform} {dev.device_kind} "
        f"x{len(devices)}, compile cache {cache_dir}; "
        f"{t_setup - t_start:.3f}s from process start to the loop's set-up "
        f"(imports, device, pins)")
    loop.setup(run)
    setup_s = time.perf_counter() - t_start
    su = watch.phase("setup")
    log(f"set-up {setup_s:.3f}s: {su['compiles']} backend compiles "
        f"({su['compile_s']:.3f}s), {su['cache_hits']} persistent-cache hits;"
        f" {json.dumps(run.notes)}")

    watch.mark("window")
    trace_dir = harness.OUT / "trace" / f"{workload}-{seed}"
    if trace:
        window = min(seconds, spec["traffic"].get("trace_seconds", seconds))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    else:
        window = seconds
    win = loop.window(run, window, _annotate)
    if trace:
        jax.profiler.stop_trace()
    watch.mark("check")
    stats = dev.memory_stats() or {}
    wc = watch.phase("window")
    calls = win["call_s"]
    log(f"window {win['elapsed_s']:.3f}s: {json.dumps(win['counters'])}; "
        f"call seconds {[round(float(c), 4) for c in calls]}; "
        f"{wc['compiles']} backend compiles and {wc['cache_hits']} "
        f"persistent-cache loads in the window")
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} (what this "
        f"runtime counts here is unexplained: compiled programs' temporaries"
        f" show under peak_bytes_reserved "
        f"{stats.get('peak_bytes_reserved')})")

    t_ref = time.perf_counter()
    checks = loop.check(run, control)
    log(f"reference comparison {time.perf_counter() - t_ref:.3f}s")
    correct = harness.judge(checks)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(run.failed)}
    if not trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in spec["end_to_end"]:
            if m["name"] in win["rates"]:
                metrics[m["name"]] = {"value": win["rates"][m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    else:
        import trace_reduce
        t_red = time.perf_counter()
        path = trace_reduce.find_trace(trace_dir)
        red = trace_reduce.reduce(trace_reduce.load(path))
        log(f"trace {path.stat().st_size} bytes, reduced in "
            f"{time.perf_counter() - t_red:.3f}s")
        ctx = SimpleNamespace(trace=red, counters=win["counters"],
                              compiles_in_window=wc["compiles"]
                              + wc["cache_hits"], notes=run.notes)
        metrics = {}
        for m in spec["per_layer"]:
            value = harness.metric_reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device, busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = trace_reduce.breakdown(red)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result, run, loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
