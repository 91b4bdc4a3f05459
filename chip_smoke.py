#!/usr/bin/env python3
"""Chip smoke test: the DIMM-fleet timing-table service on a TPU at FULL
geometry, end to end through its normal entry points, in one process.

    python3 chip_smoke.py              # one chip: the fleet service
    python3 chip_smoke.py --chips 4    # the DIMM-sharded scan on four chips

One chip: a ``synthetic_fleet`` at ``geometry.FULL`` (512x512 mats, 16 mats,
8 subarrays) is ingested by ``FleetServer`` in several chunks (campaign
telemetry through the ``fail_prob`` kernel, ``bit_signature`` matching,
generation founding, hit and conventional sweeps), aged past its staleness
horizon by one ``tick`` and queried.  A ``stream_secded_scrub`` chunk and
``memsim.system_speedup_population`` then run on the served tables.  Checks:

* the served prefix equals the same-device dense oracle bit for bit
  (``serve.oracle_parity``), after ingest and again after the tick;
* a few DIMMs of the dense oracle agree with the NumPy walker
  (``profiling.diva_profile_loop``, run in CPU-only worker processes while
  the chip works) in all but ``REF_TOLERANCE`` tables: TPU float32
  transcendentals may differ from the host's in the last ulp, which can
  flip a draw that sits on its threshold;
* the scrub corrects every injected single-bit flip and restores the
  tables' bits;
* memsim speedups of a few distinct served tables equal the NumPy walker's
  (``memsim.reference.system_speedup_loop``) bit for bit;
* every kernel trace took the compiled ``tpu-mosaic`` route, and the
  ``fail_prob``, ``bit_signature``, ``secded_*`` and ``bank_sched`` kernels
  all ran.

``--chips 4`` runs only ``stream_profile_population`` over a 4-device DIMM
mesh against the same scan with no mesh, and requires bit-identical tables.

The script exits nonzero, printing no result line, unless JAX runs on a TPU
through the compiled Mosaic route with no backend override.  The last line
of standard output is ``{"ok": true, "device": {...}}``.  Times printed are
smoke timings of one process, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: how many dense-oracle DIMM tables may differ from the NumPy walker's
REF_TOLERANCE = 1
#: kernels the one-chip run must trace on the compiled route
MAIN_PATH_KERNELS = ("fail_prob", "bit_signature", "secded_encode",
                     "secded_syndrome", "bank_sched")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ----------------------------------------------- NumPy reference (workers)

def _worker_init(src: str) -> None:
    """CPU-only worker: the chip belongs to the parent process."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, src)


def _reference_table(geometry: str, n: int, seed: int, i: int) -> list:
    """DIMM ``i``'s table from the NumPy walker at fleet age 0."""
    from repro.core import geometry as geo
    from repro.core.population import fleet_dimm_model, synthetic_fleet
    from repro.core.profiling import diva_profile_loop
    fleet = synthetic_fleet(n, getattr(geo, geometry), seed=seed)
    tp = diva_profile_loop(fleet_dimm_model(fleet.chunk(i, i + 1), 0),
                           temp_C=55.0)
    return [tp.trcd, tp.tras, tp.trp, tp.twr]


# ------------------------------------------------------------ the phases

def _table_codewords(tables):
    """Served tables as SECDED data words: (N, 64) bits of their float32
    bytes, 8 bytes per word."""
    import numpy as np

    from repro.core.ecc import bytes_to_bits
    raw = np.ascontiguousarray(tables, np.float32).view(np.uint8)
    return bytes_to_bits(raw.reshape(-1, 8))


def scrub_tables(tables) -> dict:
    """Encode the served tables with the SECDED kernel, flip one bit in
    every third codeword, and scrub them in one streamed chunk."""
    import numpy as np

    from repro.core.streaming import stream_secded_scrub
    from repro.kernels import ops
    data = _table_codewords(tables)
    code = np.concatenate([data, np.asarray(ops.secded_encode(data))], 1)
    hit = np.arange(0, len(code), 3)
    code[hit, (hit * 7) % code.shape[1]] ^= 1
    res = stream_secded_scrub(code, chunk_size=len(code), collect=True)
    restored = bool(np.array_equal(res["codewords"][:, :64], data))
    return {"words": len(code), "injected": len(hit),
            "corrected": res["corrected"],
            "uncorrectable": res["uncorrectable"], "restored": restored,
            "ok": (res["corrected"] == len(hit) and restored
                   and res["uncorrectable"] == 0)}


def memsim_tables(tables, n_requests: int, n_ref: int) -> dict:
    """The FR-FCFS speedup grid over every served table, plus exact parity
    of a few distinct tables against the NumPy walker."""
    import numpy as np

    from repro.memsim import reference, sim
    fused = sim.system_speedup_population(tables, n_requests=n_requests)
    distinct = np.unique(np.asarray(tables, np.float32), axis=0)[:n_ref]
    few = sim.system_speedup_population(distinct, n_requests=n_requests)
    loop = reference.system_speedup_loop(distinct, n_requests=n_requests,
                                         scheduler="frfcfs")
    exact = bool(np.array_equal(few["per_dimm_workload_speedup"],
                                loop["per_dimm_workload_speedup"]))
    sp = fused["per_dimm_speedup"]
    return {"n_tables": len(sp), "mean_speedup": fused["mean_speedup"],
            "min_speedup": fused["min_speedup"],
            "max_speedup": fused["max_speedup"],
            "n_ref_tables": len(distinct), "ref_exact": exact,
            "ok": exact and bool(np.isfinite(sp).all())}


def run_fleet(geometry: str, n: int, chunk: int, n_prefix: int,
              ref_dimms: tuple, n_queries: int, n_requests: int,
              seed: int = 0) -> dict:
    """The one-chip phases; fails on the first broken check."""
    import concurrent.futures as cf
    import multiprocessing as mp

    import numpy as np

    from repro.core import geometry as geo
    from repro.core.population import synthetic_fleet
    from repro.core.substrate import profile_population_arrays
    from repro.serve import (FleetConfig, FleetServer, oracle_parity,
                             take_batch)

    fleet = synthetic_fleet(n, getattr(geo, geometry), seed=seed)
    server = FleetServer(fleet, FleetConfig(chunk_size=chunk))
    walls: dict[str, float] = {}
    out: dict = {"geometry": geometry, "n_dimms": n, "chunk": chunk}

    pool = cf.ProcessPoolExecutor(
        max_workers=len(ref_dimms), mp_context=mp.get_context("spawn"),
        initializer=_worker_init, initargs=(str(SRC),))
    try:
        refs = [pool.submit(_reference_table, geometry, n, seed, i)
                for i in ref_dimms]

        t0 = time.perf_counter()
        ing = server.ingest(now=0.0)
        walls["ingest_s"] = time.perf_counter() - t0
        out["paths"] = {k: ing[k] for k in ("hits", "misses",
                                            "conventional")}
        out["generations"] = ing["n_generations"]
        out["n_chunks"] = -(-n // chunk)
        log(f"ingest: {n} DIMMs in {out['n_chunks']} chunks of {chunk}: "
            f"HIT={ing['hits']} DISCOVER={ing['misses']} "
            f"CONVENTIONAL={ing['conventional']}, "
            f"{ing['n_generations']} generations")
        if out["n_chunks"] < 2 or ing["hits"] == 0 or ing["misses"] == 0:
            fail("the fleet must span two chunks with HIT and DISCOVER paths")

        t0 = time.perf_counter()
        par = oracle_parity(server, n_prefix)
        walls["parity_ingest_s"] = time.perf_counter() - t0
        log(f"oracle parity after ingest: {par['n_mismatch']} of "
            f"{par['n_prefix']} prefix tables differ")
        out["parity_ingest"] = par

        # dense oracle of the reference DIMMs at the age they were served
        ref_batch = take_batch(fleet.chunk(0, max(ref_dimms) + 1),
                               list(ref_dimms))
        dense = np.asarray(profile_population_arrays(
            ref_batch, region="worst", temp_C=55.0, refresh_ms=64.0,
            guard_cycles=1, multibit_only=True), np.float32)[:, :4]

        bound = server.staleness()["bound_years"]
        t0 = time.perf_counter()
        tick = server.tick(bound)
        walls["tick_s"] = time.perf_counter() - t0
        rep = server.staleness(bound)
        log(f"tick at {bound:.3f}y: re-profiled {tick['reprofiled']}, "
            f"{rep['n_overdue']} overdue")
        if tick["reprofiled"] == 0 or rep["n_overdue"] != 0:
            fail("the tick past the horizon must re-profile every due DIMM")
        out["reprofiled"] = tick["reprofiled"]
        par2 = oracle_parity(server, n_prefix)
        log(f"oracle parity after tick: {par2['n_mismatch']} of "
            f"{par2['n_prefix']} prefix tables differ")
        out["parity_tick"] = par2
        if not (par["parity"] and par2["parity"]):
            fail("served tables differ from the same-device dense oracle")

        rng = np.random.default_rng(seed)
        serials = rng.integers(0, n, n_queries)
        t0 = time.perf_counter()
        got = server.query_batch(serials)
        walls["query_s"] = time.perf_counter() - t0
        want = server.state.view("table")[server.state.rows_for(serials)]
        if got.shape != (n_queries, 4) or not np.array_equal(got, want):
            fail("query_batch disagrees with the served state")
        log(f"queries: {n_queries} lookups answered")
        tables = np.array(server.state.view("table"), np.float32)

        t0 = time.perf_counter()
        scrub = scrub_tables(tables)
        walls["scrub_s"] = time.perf_counter() - t0
        log(f"scrub: {scrub}")
        if not scrub["ok"]:
            fail("the SECDED scrub did not restore the served tables")
        out["scrub"] = scrub

        t0 = time.perf_counter()
        mem = memsim_tables(tables, n_requests, n_ref=3)
        walls["memsim_s"] = time.perf_counter() - t0
        log(f"memsim: {mem}")
        if not mem["ok"]:
            fail("memsim speedups differ from the NumPy walker")
        out["memsim"] = mem

        t0 = time.perf_counter()
        ref = np.asarray([f.result() for f in refs], np.float32)
        walls["reference_wait_s"] = time.perf_counter() - t0
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    bad = int((ref != dense).any(axis=1).sum())
    log(f"NumPy walker vs dense oracle: {bad} of {len(ref_dimms)} DIMM "
        f"tables differ (tolerance {REF_TOLERANCE})")
    if bad > REF_TOLERANCE:
        fail(f"{bad} dense-oracle tables differ from the NumPy walker")
    out["reference_mismatch"] = bad
    out["smoke_wall_s"] = walls
    return out


def run_sharded(geometry: str, n: int, chunk: int, n_devices: int,
                seed: int = 0) -> dict:
    """The DIMM-sharded streamed profile against the unsharded scan."""
    import numpy as np

    from repro.core import geometry as geo
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import stream_profile_population
    from repro.sharding import dimm_mesh

    fleet = synthetic_fleet(n, getattr(geo, geometry), seed=seed)
    t0 = time.perf_counter()
    sharded = stream_profile_population(fleet, chunk_size=chunk,
                                        mesh=dimm_mesh(n_devices),
                                        collect=True)["tables"]
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = stream_profile_population(fleet, chunk_size=chunk,
                                       collect=True)["tables"]
    t_single = time.perf_counter() - t0
    bad = int((sharded != single).any(axis=1).sum())
    log(f"sharded over {n_devices} devices vs unsharded: {bad} of {n} "
        f"tables differ (smoke wall {t_sharded:.1f}s vs {t_single:.1f}s)")
    if sharded.shape != single.shape or bad:
        fail("the sharded scan is not bit-identical to the unsharded one")
    return {"n_dimms": n, "chunk": chunk, "devices": n_devices,
            "mismatch": bad,
            "smoke_wall_s": {"sharded": t_sharded, "single": t_single}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the DIMM-sharded scan on four chips")
    args = ap.parse_args(argv)

    for var in ("REPRO_FORCE_REF", "REPRO_BACKEND"):
        if os.environ.get(var):
            fail(f"{var} is set: the smoke runs the platform's own route")
    import jax
    if jax.default_backend() != "tpu":
        fail(f"JAX found no TPU (backend {jax.default_backend()!r})")
    devices = jax.devices()
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices; "
             f"JAX sees {len(devices)}")

    sys.path.insert(0, str(SRC))
    from repro import obs
    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops
    if ops.backend_tag() != "tpu-mosaic":
        fail(f"kernel route is {ops.backend_tag()!r}, not 'tpu-mosaic'")
    cache_dir = enable_compile_cache()
    compiles = {"backend_compile_s": 0.0, "compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **kw):
        if event == _COMPILE_EVENT:
            compiles["backend_compile_s"] += duration
            compiles["compiles"] += 1

    def on_event(event, **kw):
        if event == _CACHE_HIT_EVENT:
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        res = run_sharded("FULL", n=256, chunk=64, n_devices=4)
        count = 4
    else:
        res = run_fleet("FULL", n=192, chunk=64, n_prefix=128,
                        ref_dimms=(0, 5, 10), n_queries=4096,
                        n_requests=2000)
        count = len(devices)
        fam = obs.REGISTRY.get("repro_kernel_traces_total")
        traced = {lv for lv, _ in fam._series() if lv}
        log(f"kernel traces (kernel, backend): {sorted(traced)}")
        if any(backend != "tpu-mosaic" for _, backend in traced):
            fail("a kernel trace took a route other than tpu-mosaic")
        missing = set(MAIN_PATH_KERNELS) - {k for k, _ in traced}
        if missing:
            fail(f"main-path kernels never traced: {sorted(missing)}")
    wall = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log(f"smoke timings (one process, not a benchmark): total {wall:.1f}s, "
        f"backend compile {compiles['backend_compile_s']:.1f}s over "
        f"{compiles['compiles']} programs, {compiles['cache_hits']} "
        f"persistent-cache hits; phases {res['smoke_wall_s']}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
