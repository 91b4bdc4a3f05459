"""Loop ``ingest``: new DIMMs arriving at a fleet whose generations are
known.  A closed loop of ``FleetServer.ingest`` calls, each one chunk of
``dimms_per_call`` new DIMMs, into a server whose generation cache the
set-up filled.

The DIMMs are the configuration's fleet (its ``fleet_seed``), the same for
every run, and of them only the serials of the traffic's ``designs``
arrive.  Set-up ingests the first chunks of them in serial order until
one founds no generation, so every run starts from the same generation
cache.  The seed orders the arrivals after that: each later chunk holds
the same DIMMs for every seed, in an order drawn from the seed, so every
seed brings the same work (a chunk's cost depends on which DIMMs it
holds: one with DIMMs that match no generation runs discovery, seconds
more).

Traffic keys: ``designs``, ``arrivals`` (the stream's length; only the
DIMMs reached are made), ``dimms_per_call``, ``now_years`` (the fleet age
of every ingest), ``founding_max_chunks``, ``warm_every_row_counts``
(sizes of the every-row sweep that set-up runs once, so that no chunk of
the window compiles one) and ``check`` (the mismatch limit).
"""
from __future__ import annotations

import time

import numpy as np

import fleetcell
from harness import BenchError

END_TO_END = "ingest_dimms_per_s"


def check_pins(run) -> None:
    fleetcell.check_pins(run.config)


def setup(run) -> None:
    tr = run.traffic
    n, now = tr["dimms_per_call"], tr["now_years"]
    run.order = fleetcell.arrivals(run.config, tr, tr["arrivals"])
    run.base = fleetcell.fleet(run.config, int(run.order[-1]) + 1,
                               run.config["fleet_seed"])
    srv = run.server = fleetcell.server(
        run.config, fleetcell.arrival_stream(run.base, run.order))
    t0 = time.perf_counter()
    founding = []
    for _ in range(tr["founding_max_chunks"]):
        gens = srv.cache.n_generations
        founding.append(srv.ingest(n, now=now))
        if srv.cache.n_generations == gens:
            break
    else:
        raise BenchError(f"generations still founded after "
                         f"{len(founding)} set-up chunks")
    t1 = time.perf_counter()
    lo = srv._ingested
    rest = run.order[lo:lo + (len(run.order) - lo) // n * n].reshape(-1, n)
    run.order[lo:lo + rest.size] = np.random.default_rng(run.seed).permuted(
        rest, axis=1).ravel()
    from repro.serve import take_batch
    first = run.order[:n]
    first = take_batch(run.base.chunk(0, int(first[-1]) + 1), first)
    for k in tr["warm_every_row_counts"]:
        srv._profile_all_rows(take_batch(first, np.arange(k)), now)
    run.notes["setup_phases_s"] = {"founding": t1 - t0,
                                   "warm_every_row": time.perf_counter() - t1}
    run.notes["setup_chunks"] = len(founding)
    run.notes["setup_paths"] = [
        {k: r[k] for k in ("hits", "misses", "conventional")}
        for r in founding]
    run.notes["generations"] = srv.cache.n_generations


def window(run, seconds: float, annotate) -> dict:
    srv, tr = run.server, run.traffic
    n, now = tr["dimms_per_call"], tr["now_years"]
    before = srv.metrics()["paths"]
    run.lo = srv._ingested
    dimms = 0
    ends = []
    every_row = []
    t0 = time.perf_counter()
    while True:
        with annotate("bench.ingest_chunk"):
            res = srv.ingest(n, now=now)
        ends.append(time.perf_counter() - t0)
        dimms += res["ingested"]
        every_row.append(res["conventional"])
        if ends[-1] >= seconds:
            break
    elapsed, calls = ends[-1], len(ends)
    after = srv.metrics()["paths"]
    run.hi = srv._ingested
    paths = {k: after[k] - before[k] for k in after}
    run.notes["window_paths"] = paths
    run.notes["window_chunks"] = calls
    return {"elapsed_s": elapsed, "attempted": dimms,
            "call_s": list(np.diff(ends, prepend=0.0)),
            "rates": {END_TO_END: dimms / elapsed},
            "counters": {"paths": paths, "chunks": calls,
                         "every_row_per_chunk": every_row}}


def check(run, control: str | None = None) -> dict:
    rec = fleetcell.served(run.server, run.order[run.lo:run.hi])
    run.failed = rec["missing"]
    return fleetcell.check_numbers(run.config, run.traffic, run.base,
                                   [(rec, run.traffic["now_years"])],
                                   control)
