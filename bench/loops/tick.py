"""Loop ``tick``: aging-driven re-profiling.  Set-up ingests
``setup_chunks`` chunks of ``dimms_per_call`` DIMMs of the configuration's
fleet at age 0, the first serials of the traffic's ``designs``, and runs
one tick; every tick of the window then advances
the fleet clock by the fleet's staleness bound
(``staleness()["bound_years"]``), so every DIMM is due at every tick and
each tick re-profiles the whole fleet.

The fleet (``fleet_seed``), and so the work of a tick, is the same for
every run; the seed sets the fleet age of the ticks: the set-up tick comes
at the bound plus eighths of it drawn from the seed.  The served tables
of the window's first and last ticks are compared with the plain
reference at the ages they were served (after a few ticks the fleet is
past its service life and every table is the standard one; the first
ticks still serve reduced tables, so the comparison takes the first
``check["first_ticks"]`` ticks and the last).

Traffic keys: ``designs``, ``dimms_per_call``, ``setup_chunks`` and
``check``.
"""
from __future__ import annotations

import time

import numpy as np

import fleetcell

END_TO_END = "reprofile_dimms_per_s"


def check_pins(run) -> None:
    fleetcell.check_pins(run.config)


def setup(run) -> None:
    tr = run.traffic
    t0 = time.perf_counter()
    order = fleetcell.arrivals(run.config, tr,
                               tr["dimms_per_call"] * tr["setup_chunks"])
    run.base = fleetcell.fleet(run.config, int(order[-1]) + 1,
                               run.config["fleet_seed"])
    srv = run.server = fleetcell.server(
        run.config, fleetcell.arrival_stream(run.base, order))
    for _ in range(tr["setup_chunks"]):
        srv.ingest(tr["dimms_per_call"], now=0.0)
    # a tick reads its DIMMs back from the server's stream by serial, so
    # once the arrivals are in, the stream is the fleet itself
    srv.stream = run.base
    t1 = time.perf_counter()
    run.bound = srv.staleness()["bound_years"]
    # eighths of the bound keep every tick's age exact in float32: the
    # server keeps due dates in float32, and a due date that float32 rounds
    # up is not due at a tick at that very age (PERF.md, Open questions)
    frac = np.random.default_rng(run.seed).integers(0, 8) / 8.0
    run.clock = run.bound * (1.0 + frac)
    warm = srv.tick(run.clock)
    run.notes["setup_phases_s"] = {"ingest": t1 - t0,
                                   "tick": time.perf_counter() - t1}
    run.notes["setup_reprofiled"] = warm["reprofiled"]
    run.notes["bound_years"] = run.bound
    run.notes["setup_tick_years"] = run.clock
    run.notes["paths"] = srv.metrics()["paths"]


def _snapshot(run) -> tuple:
    serials = np.sort(np.asarray(run.server.state.view("serial"), np.int64))
    return fleetcell.served(run.server, serials), run.clock


def window(run, seconds: float, annotate) -> dict:
    srv = run.server
    dimms = 0
    ends = []
    run.snaps = []
    t0 = time.perf_counter()
    while True:
        run.clock += run.bound
        with annotate("bench.tick"):
            dimms += srv.tick(run.clock)["reprofiled"]
        if len(ends) < run.traffic["check"]["first_ticks"]:
            run.snaps.append(_snapshot(run))
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    elapsed, ticks = ends[-1], len(ends)
    if ticks > len(run.snaps):
        run.snaps.append(_snapshot(run))
    run.notes["window_ticks"] = ticks
    run.notes["last_age_years"] = run.clock
    return {"elapsed_s": elapsed, "attempted": dimms,
            "call_s": list(np.diff(ends, prepend=0.0)),
            "rates": {END_TO_END: dimms / elapsed},
            "counters": {"ticks": ticks, "reprofiled": dimms}}


def check(run, control: str | None = None) -> dict:
    run.failed = sum(r["missing"] for r, _ in run.snaps)
    return fleetcell.check_numbers(run.config, run.traffic, run.base,
                                   run.snaps, control)
