"""Fleet-serving bench: online timing-table queries over a live DIMM fleet.

Stands up a ``repro.serve.FleetServer`` over a synthetic fleet, ingests the
whole population through the chunked streaming substrate, then gates on the
serving contracts:

  * throughput — sustained timing-table queries/sec (batched gathers over
    random serials) must stay >= the --min-qps floor (default 1,000/s on
    the 10k-DIMM fleet of the committed trajectory);
  * bounded staleness — after every re-profiling tick, no DIMM's table age
    may exceed the fleet's staleness bound (its worst re-profile horizon)
    plus one tick interval;
  * oracle parity — on a dense-profiled prefix of the fleet, every
    hit/discover-path table must equal the geometry-oracle ``diva_profile``
    table (region="worst") bit for bit, and every conventional-path table
    the every-row oracle (region="all");
  * checkpoint roundtrip — a save/load cycle into a fresh server must
    reproduce tables, labels, and counters exactly;
  * metrics consistency — ``FleetServer.metrics()`` (the obs-registry view)
    must agree with every gate value this script computes independently:
    path counts vs the ingest stats, query counter vs the throughput loop,
    the staleness gauge vs ``staleness()``, the re-profile counter vs the
    tick sum, and the registry's chunk-cache compile counts vs the actual
    ``substrate._CHUNK_JIT_CACHE`` keys (one lowering per key).

Appends the record to ``benchmarks/BENCH_serve.json`` and exits nonzero on
any gate failure:

    PYTHONPATH=src python benchmarks/serve_bench.py \\
        --fleet 10000 --chunk 512 --budget-mb 4096

``--smoke`` is the CI-sized run (256 DIMMs, no trajectory append).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from kernel_bench import backend_tag  # noqa: E402

TICK_DT_YEARS = 1.0          # re-profiling cadence of the bench's fleet life
LIFE_YEARS = 3.0             # ticks at 1.0 .. 3.0 (past the 2.5y horizon)


def _checkpoint_roundtrip(server) -> bool:
    """save -> load into a fresh server over the same stream; tables,
    labels, paths, counters, and pending deadlines must survive exactly."""
    from repro.serve import FleetServer
    with tempfile.TemporaryDirectory() as d:
        saver = FleetServer(server.stream, server.cfg, checkpoint_dir=d)
        saver.load_state(server.state_dict())
        saver.save(step=0)
        restored = FleetServer(server.stream, server.cfg, checkpoint_dir=d)
        restored.load()
        a, b = server.state_dict(), restored.state_dict()
        return all(np.array_equal(a[k], b[k]) for k in a)


def bench_serve(n_dimms: int, chunk_size: int, budget_mb: int,
                min_qps: float, out_path: Path | None,
                metrics_out: str | None = None,
                trace_out: str | None = None) -> dict:
    from repro import obs
    from repro.core import substrate
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.serve import FleetConfig, FleetServer, oracle_parity

    recording = contextlib.ExitStack()
    if trace_out:
        recording.enter_context(obs.record(trace_out))

    fleet = synthetic_fleet(n_dimms, TINY, seed=0)
    server = FleetServer(fleet, FleetConfig(chunk_size=chunk_size))

    # ---- ingest: every DIMM gets a table through its cheapest path
    t0 = time.perf_counter()
    ingest = server.ingest(now=0.0)
    t_ingest = time.perf_counter() - t0

    # ---- oracle parity on a dense-profiled prefix (before any aging)
    parity = oracle_parity(server, min(n_dimms, 512))

    # ---- staleness: walk the fleet clock past every re-profile horizon;
    # after each tick no table may be older than the bound + one tick
    bound = server.staleness()["bound_years"]
    ticks = []
    stale_ok = True
    max_seen = 0.0
    t0 = time.perf_counter()
    for k in range(1, int(LIFE_YEARS / TICK_DT_YEARS) + 1):
        now = k * TICK_DT_YEARS
        tick = server.tick(now)
        rep = server.staleness(now)
        max_seen = max(max_seen, rep["max_staleness_years"])
        stale_ok &= rep["max_staleness_years"] <= bound + TICK_DT_YEARS
        ticks.append({"now": now, "reprofiled": tick["reprofiled"],
                      "max_staleness_years": rep["max_staleness_years"]})
    t_tick = time.perf_counter() - t0

    # ---- query throughput: batched table gathers over random serials
    rng = np.random.default_rng(0)
    n_queries = 0
    t0 = time.perf_counter()
    while True:
        serials = rng.integers(0, n_dimms, 4096)
        tab = server.query_batch(serials)
        assert tab.shape == (4096, 4)
        n_queries += 4096
        elapsed = time.perf_counter() - t0
        if elapsed > 1.0 and n_queries >= 16384:
            break
    qps = n_queries / elapsed

    # ---- checkpoint roundtrip through the ECC-protected manager
    ckpt_ok = _checkpoint_roundtrip(server)

    # ---- metrics consistency: the obs-registry view of this server must
    # match every number computed independently above, and the registry's
    # chunk-compile accounting must match the actual cache (one lowering
    # per (entry, statics, donate) key — the one-compiled-program contract)
    met = server.metrics()
    cache_counts: dict[str, int] = {}
    for k in substrate._CHUNK_JIT_CACHE:
        cache_counts[k[0]] = cache_counts.get(k[0], 0) + 1
    checks = {
        "paths": met["paths"] == {"hit": int(ingest["hits"]),
                                  "discover": int(ingest["misses"]),
                                  "conventional": int(ingest["conventional"])},
        "queries": met["queries"] == n_queries,
        "staleness_gauge": met["max_table_age_years"]
        == server.staleness()["max_staleness_years"],
        "reprofiled": met["reprofiled"]
        == sum(t["reprofiled"] for t in ticks),
        "compiles": met["chunk_compiles"] == cache_counts,
        "latency_count": met["query_latency_seconds"]["count"] > 0,
    }
    metrics_ok = all(checks.values())

    peak_mb = obs.peak_rss_mb()
    entry = {
        "date": time.strftime("%Y-%m-%d"),
        "backend": backend_tag(),
        "geometry": "TINY",
        "n_dimms": int(n_dimms),
        "chunk_size": int(chunk_size),
        "n_chunks": int(-(-n_dimms // chunk_size)),
        "profile_s": round(t_ingest, 2),
        "ingest_dimms_per_s": round(n_dimms / max(t_ingest, 1e-9), 1),
        "hits": int(ingest["hits"]),
        "misses": int(ingest["misses"]),
        "conventional": int(ingest["conventional"]),
        "n_generations": int(ingest["n_generations"]),
        "tick_s": round(t_tick, 2),
        "reprofiled": int(sum(t["reprofiled"] for t in ticks)),
        "staleness_bound_years": round(float(bound), 3),
        "max_staleness_years": round(float(max_seen), 3),
        "staleness_bounded": bool(stale_ok),
        "queries_per_s": round(qps, 1),
        "n_queries": int(n_queries),
        "ckpt_roundtrip_ok": bool(ckpt_ok),
        "budget_mb": int(budget_mb),
        "peak_rss_mb": round(peak_mb, 1),
        "prefix_parity": bool(parity["parity"]),
        "metrics": {
            "paths": {k: int(v) for k, v in met["paths"].items()},
            "hit_rate": round(float(met["hit_rate"]), 4),
            "queries": int(met["queries"]),
            "query_latency_p50_us": round(
                met["query_latency_seconds"]["p50"] * 1e6, 1),
            "query_latency_p99_us": round(
                met["query_latency_seconds"]["p99"] * 1e6, 1),
            "max_table_age_years": round(
                float(met["max_table_age_years"]), 3),
            "reprofiled": int(met["reprofiled"]),
            "chunk_compiles": {k: int(v)
                               for k, v in met["chunk_compiles"].items()},
            "consistent": bool(metrics_ok),
        },
    }
    if out_path is not None:
        history = []
        if out_path.exists():
            history = json.loads(out_path.read_text())
        history.append(entry)
        out_path.write_text(json.dumps(history, indent=2) + "\n")
    print(json.dumps(entry, indent=2))

    failures = []
    if not parity["parity"]:
        failures.append(f"{parity['n_mismatch']}/{parity['n_prefix']} "
                        "prefix tables differ from the dense oracle")
    if not stale_ok:
        failures.append(f"staleness {max_seen:.3f}y exceeded the "
                        f"{bound:.3f}y bound + {TICK_DT_YEARS}y tick")
    if qps < min_qps:
        failures.append(f"throughput {qps:.0f} queries/s < {min_qps:.0f}/s")
    if not ckpt_ok:
        failures.append("checkpoint roundtrip altered serving state")
    if not metrics_ok:
        bad = sorted(k for k, v in checks.items() if not v)
        failures.append("FleetServer.metrics() disagrees with the "
                        f"independently computed gate values: {bad}")
    if peak_mb > budget_mb:
        failures.append(f"peak RSS {peak_mb:.0f} MB exceeds the "
                        f"{budget_mb} MB budget")
    if trace_out:
        recording.close()
        print(f"trace  -> {trace_out}")
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(obs.REGISTRY.prometheus_text())
        print(f"metrics -> {metrics_out}")
    if failures:
        sys.exit("FAIL: " + "; ".join(failures))
    print(f"OK: {n_dimms}-DIMM fleet served at {qps:.0f} queries/s "
          f"(hits={ingest['hits']} misses={ingest['misses']} "
          f"conventional={ingest['conventional']}), staleness bounded at "
          f"{bound:.2f}y, checkpoint roundtrip exact"
          + (f", trajectory -> {out_path}" if out_path is not None else ""))
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fleet; gates only, no trajectory append")
    ap.add_argument("--fleet", type=int, default=10_000)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--budget-mb", type=int, default=4096)
    ap.add_argument("--min-qps", type=float, default=1000.0)
    ap.add_argument("--out", default=str(Path(__file__).parent
                                         / "BENCH_serve.json"))
    ap.add_argument("--metrics-out", default=None,
                    help="write the obs registry as Prometheus text here")
    ap.add_argument("--trace-out", default=None,
                    help="record a profiler trace (spans and device "
                         "events); write Chrome trace-event JSON here")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        bench_serve(256, 128, args.budget_mb, args.min_qps, out_path=None,
                    metrics_out=args.metrics_out, trace_out=args.trace_out)
        return
    bench_serve(args.fleet, args.chunk, args.budget_mb, args.min_qps,
                Path(args.out), metrics_out=args.metrics_out,
                trace_out=args.trace_out)


if __name__ == "__main__":
    main()
