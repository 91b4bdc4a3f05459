"""Kernel micro-benchmarks (interpret mode on CPU; numbers are for CI
tracking, not TPU performance — the roofline story lives in EXPERIMENTS.md).

``--smoke`` times the tentpoles: one jitted ``profile_population`` sweep over
a DIMM population vs the legacy per-DIMM NumPy walker, one jitted
``shuffling_gain_population`` call vs the per-access ``shuffling_gain_loop``,
one jitted ``lifetime_population`` epoch scan vs the per-DIMM Python
lifecycle ``lifetime_loop``, one jitted ``recover_mapping_population``
scramble recovery vs the per-subarray ``estimate_row_mapping`` loop, and one
fused ``memsim.system_speedup_population`` grid vs the retained per-request
in-order reference walker (``memsim.reference.system_speedup_loop``), and one
streamed ``stream_profile_population`` scan over a stream of fleet sizes vs
the dense path's per-size re-lowering, and one batched N-axis
``operating_grid_arrays`` sweep vs the per-(DIMM, point) NumPy
``operating_point_eval`` loop; CI asserts all seven stay >= 5x on CPU with
bit-identical results (decision-for-decision for the operating grid, whose
lambdas are float32 reductions).  A ninth gate times the streamed chunk
scan with the obs metrics registry enabled vs disabled
(``obs_overhead_smoke``): tables must stay bit-identical, zero new chunk
programs may lower, and the wall-time delta must stay under 2%.  A tenth
gate (``kernel_route_smoke``) checks the backend-dispatch story itself: the
registry-dispatched default CPU route (``cpu-ref`` jnp oracles) must beat
the forced ``cpu-pallas-interpret`` route >= 5x on two integer kernels with
bit-identical outputs — the measured reason the CPU default is the oracle.

    PYTHONPATH=src python benchmarks/kernel_bench.py --smoke

``--bench-kernels`` times the eight registry dispatch sites under every
backend route available on this host (``ops.valid_tags()``) and appends one
row per (kernel, backend) to ``benchmarks/BENCH_kernels.json`` — the
committed per-backend kernel trajectory (``run.py --check`` validates the
schema and that every backend covers all eight kernels):

    PYTHONPATH=src python benchmarks/kernel_bench.py --bench-kernels

``--bench-streaming`` runs the fleet-scale streaming trajectory (profile +
generation discovery of a ``--fleet``-sized synthetic population under a
``--budget-mb`` peak-RSS budget) and appends the throughput record to
``benchmarks/BENCH_streaming.json``:

    PYTHONPATH=src python benchmarks/kernel_bench.py --bench-streaming \\
        --fleet 1000000 --chunk 4096 --budget-mb 4096
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def backend_tag() -> str:
    """The resolved dispatch tag for benchmark rows — a thin re-export of
    ``kernels.ops.backend_tag`` (the single backend authority), so bench and
    dispatch can never disagree.  This replaces the local reimplementation
    that used to live here; ``serve_bench.py`` still imports it from this
    module."""
    from repro.kernels import ops
    return ops.backend_tag()


def _bench(fn, *args, iters=3, **kw):
    import jax
    jax.block_until_ready(fn(*args, **kw))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    # block on the WHOLE output pytree: np.asarray of one dict entry would
    # leave sibling outputs in flight and time dispatch, not compute
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def kernel_cases():
    """The eight registry dispatch sites as benchable cases, in registry
    order: ``(kernel, shape, call)`` where ``call()`` runs the dispatch site
    once on fixed inputs under whatever backend is ambient (``fail_prob``
    twice: the access channel, and with both channels).  One list feeds
    the legacy CSV dict (``kernels``), the committed per-backend trajectory
    (``bench_kernels``) and the route gate (``kernel_route_smoke``), so none
    of them can drift out of sync with ``kernels/registry.py``."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    data = rng.integers(0, 2, (4096, 64)).astype(np.int32)
    code = rng.integers(0, 2, (4096, 72)).astype(np.int32)
    bursts = rng.integers(0, 2, (1024, 576)).astype(np.int32)
    rf = np.linspace(0, 1, 256)
    r, k, v, w = (rng.normal(0, 0.3, (2, 128, 4, 32)).astype(np.float32)
                  for _ in range(4))
    u = rng.normal(0, 0.1, (4, 32)).astype(np.float32)
    row_src = rng.integers(0, 512, 512).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 8).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                      np.float32)
    op_coeffs = np.concatenate(
        [coeffs, np.array([1.2, 4.0, 0.4, 1.0, 0.3, 1.2], np.float32)])
    sig_counts = rng.integers(0, 2 ** 20, (4096, 512)).astype(np.int32)
    sched_args = (rng.integers(0, 16, 8).astype(np.int32),
                  rng.integers(0, 50, 8).astype(np.int32),
                  rng.integers(0, 2, 8).astype(np.int32),
                  rng.integers(0, 400, 8).astype(np.int32),
                  np.ones(8, bool),
                  rng.integers(-1, 50, 16).astype(np.int32),
                  rng.integers(0, 500, 16).astype(np.int32),
                  rng.integers(-100, 500, 16).astype(np.int32),
                  rng.integers(0, 500, 2).astype(np.int32),
                  rng.integers(-100, 400, 2).astype(np.int32),
                  rng.integers(-100, 400, 2).astype(np.int32),
                  np.int32(100),
                  rng.integers(4, 30, (16, 6)).astype(np.int32),
                  (np.arange(16) % 2).astype(np.int32),
                  (np.arange(16) % 2).astype(np.int32))
    return [
        ("secded_encode", "4096w", ops.secded_encode, (data,), {}),
        ("secded_syndrome", "4096w", ops.secded_syndrome, (code,), {}),
        ("fail_prob", "8x512x128", ops.fail_prob,
         (row_src, d_mat, coeffs), {"cols": 128}),
        ("fail_prob", "8x512x128_2ch", ops.fail_prob,
         (row_src, d_mat, op_coeffs),
         {"cols": 128, "voltage": True, "retention": True}),
        ("bit_signature", "4096x512", ops.bit_signature,
         (sig_counts,), {"nbits": 9}),
        ("bank_sched", "q8_b16", ops.bank_sched, sched_args,
         dict(tbl=4, trrd=5, tfaw=24, use_bus=True, use_act=True)),
        ("diva_shuffle", "1024b", ops.diva_shuffle, (bursts,), {}),
        ("rc_transient", "256c", ops.rc_transient, (rf, rf), {}),
        ("wkv6", "2x128x4x32", ops.wkv6, (r, k, v, w, u), {}),
    ]


def _bench_case(fn, args, kw, iters=3):
    """Time one dispatch site the way production callers run it: under
    ``jax.jit``, so the ambient backend resolves at TRACE time and the timed
    iterations replay the compiled program.  (Timing the eager wrapper would
    charge the oracle route for op-by-op dispatch no real caller pays —
    every entry point in core/substrate jits around these sites.)  A FRESH
    jit wrapper per call keeps one backend's compiled program from serving
    another backend's timing via the jit cache.  Returns (us_per_call,
    output pytree).

    The one oracle that is host-side NumPy under the hood
    (``ref.rc_transient`` -> ``spice.sense_time``) cannot trace; it falls
    back to eager timing, which is also exactly how its callers run it."""
    import jax
    jfn = jax.jit(lambda *a: fn(*a, **kw))
    try:
        jax.block_until_ready(jfn(*args))  # compile
    except jax.errors.TracerArrayConversionError:
        jfn = lambda *a: fn(*a, **kw)  # noqa: E731 — eager fallback
        jax.block_until_ready(jfn(*args))  # warm any inner jits
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6, out


def kernels(tag: str | None = None):
    """Legacy flat CSV dict ``{f"{kernel}_{shape}_us": us}`` over the eight
    registry sites (plus the unshuffled-layout permutation, which rides the
    ``diva_shuffle`` site); ``tag`` pins the backend route via
    ``ops.force_backend`` (None = the ambient ``backend_tag()``)."""
    import contextlib

    from repro.kernels import ops
    ctx = ops.force_backend(tag) if tag else contextlib.nullcontext()
    with ctx:
        out = {f"{name}_{shape}_us": round(_bench_case(fn, args, kw)[0], 1)
               for name, shape, fn, args, kw in kernel_cases()}
        bursts = np.random.default_rng(0).integers(
            0, 2, (1024, 576)).astype(np.int32)
        out["shuffle_permute_unshuffled_1024b_us"] = round(
            _bench_case(ops.diva_shuffle, (bursts,), {"shuffle": False})[0],
            1)
    return out


def bench_kernels(out_path: Path, tags: tuple[str, ...] | None = None,
                  iters: int = 3) -> list[dict]:
    """The committed per-backend kernel trajectory: time every registry
    dispatch site under every backend route available on this host and
    append one row per (kernel, backend) to ``BENCH_kernels.json``.

    ``speedup_vs_ref`` is ``us_ref / us_backend``, both measured in THIS
    process — >1 means the route beats the jnp oracle.  On a CPU host the
    interpret route is the semantics validator, not the fast path, so its
    speedups sit well under 1 (the measured reason ``cpu-ref`` is the CPU
    default); the compiled gpu-triton / tpu-mosaic rows are where the >1
    trajectory lives.
    """
    from repro.kernels import ops
    if tags is None:
        tags = ops.valid_tags()  # "<plat>-ref" always leads
    cases = kernel_cases()
    ref_us = {}
    with ops.force_backend(tags[0]):
        for name, shape, fn, a, kw in cases:
            ref_us[name, shape] = _bench_case(fn, a, kw, iters=iters)[0]
    date = time.strftime("%Y-%m-%d")
    rows = []
    for tag in tags:
        with ops.force_backend(tag):
            for name, shape, fn, a, kw in cases:
                us = ref_us[name, shape] if tag == tags[0] \
                    else _bench_case(fn, a, kw, iters=iters)[0]
                rows.append({
                    "date": date, "backend": tag, "kernel": name,
                    "shape": shape, "us_per_call": round(us, 1),
                    "speedup_vs_ref":
                    round(ref_us[name, shape] / max(us, 1e-9), 3)})
    history = []
    if out_path.exists():
        history = json.loads(out_path.read_text())
    history.extend(rows)
    out_path.write_text(json.dumps(history, indent=2) + "\n")
    for row in rows:
        print(f"kernel_{row['kernel']}_{row['shape']}_us,"
              f"{row['us_per_call']},backend={row['backend']};"
              f"speedup_vs_ref={row['speedup_vs_ref']}")
    return rows


def kernel_route_smoke() -> dict:
    """The backend-route gate (the tenth ``--smoke`` gate): the registry-
    dispatched default CPU route (``cpu-ref`` jnp oracles) vs the forced
    ``cpu-pallas-interpret`` route on two integer kernels.  Outputs must be
    BIT-identical (the dispatch layer may never change results, only where
    they run) and the default route >= 5x faster — the measured fact that
    flipped the CPU default from interpret-everything to the oracle graphs.
    SECDED at scrub scale (32k codewords) is where the interpret tax bites:
    the oracle is one fused XLA matmul, the interpret route replays the
    Pallas interpreter per grid step (measured 14-30x here).
    """
    import jax

    from repro.kernels import ops
    ref_tag, interp_tag = ops.valid_tags()[:2]
    rng = np.random.default_rng(1)
    cases = [
        ("secded_encode", "32768w", ops.secded_encode,
         (rng.integers(0, 2, (32768, 64)).astype(np.int32),), {}),
        ("secded_syndrome", "32768w", ops.secded_syndrome,
         (rng.integers(0, 2, (32768, 72)).astype(np.int32),), {}),
    ]
    out = {"ref_tag": ref_tag, "interpret_tag": interp_tag,
           "results_match": True, "min_speedup": float("inf")}
    for name, _, fn, a, kw in cases:
        with ops.force_backend(ref_tag):
            us_ref, got_ref = _bench_case(fn, a, kw)
        with ops.force_backend(interp_tag):
            us_int, got_int = _bench_case(fn, a, kw)
        speedup = round(us_int / max(us_ref, 1e-9), 1)
        out[f"{name}_ref_us"] = round(us_ref, 1)
        out[f"{name}_interpret_us"] = round(us_int, 1)
        out[f"{name}_speedup"] = speedup
        out["results_match"] &= all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree_util.tree_leaves(got_ref),
                            jax.tree_util.tree_leaves(got_int)))
        out["min_speedup"] = min(out["min_speedup"], speedup)
    return out


def profile_population_speedup(n_dimms: int = 8, iters: int = 1) -> dict:
    """Wall-clock: one jitted population sweep vs the per-DIMM NumPy walker.

    The legacy loop is timed on the SAME DIMMs with the SAME Monte-Carlo
    decisions (shared query hash), so the two paths do identical work — the
    difference is pure batching + jit.
    """
    from repro.core.geometry import SMALL
    from repro.core.population import make_population
    from repro.core.profiling import diva_profile_loop
    from repro.core.substrate import DimmBatch, profile_population_arrays

    pop = make_population(SMALL, n_dimms)
    batch = DimmBatch.from_population(pop)

    profile_population_arrays(batch, temp_C=55.0, multibit_only=True)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        arr = profile_population_arrays(batch, temp_C=55.0, multibit_only=True)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        legacy = [diva_profile_loop(d, temp_C=55.0) for d in pop]
    t_loop = (time.perf_counter() - t0) / iters

    match = all(tuple(row) == (tp.trcd, tp.tras, tp.trp, tp.twr)
                for row, tp in zip(np.round(arr, 6), legacy))
    return {"n_dimms": n_dimms,
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def shuffling_gain_speedup(n_dimms: int = 8, n_accesses: int = 400,
                           iters: int = 1) -> dict:
    """Wall-clock: one jitted ``shuffling_gain_population`` call vs the
    per-access NumPy double loop on the SAME profiles and counter-hash error
    draws (identical work, pure batching + kernels)."""
    from repro.core.shuffling import design_stripe_profiles, shuffling_gain_loop
    from repro.core.substrate import shuffling_gain_population

    probs = design_stripe_profiles(n_dimms)
    seeds = np.arange(n_dimms)

    shuffling_gain_population(probs, seeds=seeds, n_accesses=n_accesses)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        batched = shuffling_gain_population(probs, seeds=seeds,
                                            n_accesses=n_accesses)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        legacy = [shuffling_gain_loop(probs[d], n_accesses=n_accesses,
                                      seed=int(seeds[d]))
                  for d in range(n_dimms)]
    t_loop = (time.perf_counter() - t0) / iters

    match = all(int(batched["total"][d]) == legacy[d]["total"]
                and batched["frac_no_shuffle"][d] == legacy[d]["frac_no_shuffle"]
                and batched["frac_shuffle"][d] == legacy[d]["frac_shuffle"]
                for d in range(n_dimms))
    return {"n_dimms": n_dimms,
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def lifetime_speedup(n_dimms: int = 4, n_epochs: int = 3,
                     iters: int = 1) -> dict:
    """Wall-clock: one jitted lifetime scan (all DIMMs x all epochs) vs the
    per-DIMM Python lifecycle on the SAME aging/temperature schedule and the
    SAME Monte-Carlo decisions (shared query hash) — identical work, pure
    batching + the epoch lax.scan.
    """
    from repro.core.geometry import SMALL
    from repro.core.population import make_population
    from repro.core.profiling import lifetime_loop
    from repro.core.substrate import DimmBatch, lifetime_population

    pop = make_population(SMALL, n_dimms)
    batch = DimmBatch.from_population(pop)
    ages = np.linspace(0.0, 6.0, n_epochs).astype(np.float32)
    temps = np.full(n_epochs, 55.0)

    lifetime_population(batch, ages, temps)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = lifetime_population(batch, ages, temps)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        legacy = [lifetime_loop(d, ages, temps) for d in pop]
    t_loop = (time.perf_counter() - t0) / iters

    match = all(
        np.array_equal(out["timings"][:, d], legacy[d]["timings"])
        and np.array_equal(out["stale_fail"][:, d], legacy[d]["stale_fail"])
        for d in range(n_dimms))
    return {"n_dimms": n_dimms, "n_epochs": n_epochs,
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def recover_mapping_speedup(n_dimms: int = 24, iters: int = 1) -> dict:
    """Wall-clock: one jitted ``recover_mapping_population`` call (the blind
    scramble recovery of the whole population) vs the retained per-subarray
    ``estimate_row_mapping`` Python loop on the SAME campaign counts —
    identical work, and the decisions AND confidences must be literally
    bit-identical (integer votes + host float64 division)."""
    from repro.core.geometry import SMALL
    from repro.core.population import make_population
    from repro.core.substrate import DimmBatch
    from repro.discovery.blind import campaign_counts
    from repro.discovery.recover import (recover_mapping_loop,
                                         recover_mapping_population)

    pop = make_population(SMALL, n_dimms)
    counts, expected = campaign_counts(pop, DimmBatch.from_population(pop),
                                       t_ops=(7.5,))
    counts, expected = counts[0], expected[0]

    recover_mapping_population(counts, expected)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        rec = recover_mapping_population(counts, expected)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        loop = recover_mapping_loop(counts, expected)
    t_loop = (time.perf_counter() - t0) / iters

    match = all(np.array_equal(rec[k], loop[k]) for k in
                ("ext_bit", "xor", "confidence", "n_significant_pairs",
                 "est_ext_to_int"))
    return {"n_dimms": n_dimms, "n_subarrays": counts.shape[1],
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def memsim_grid_speedup(n_dimms: int = 3, n_requests: int = 250,
                        iters: int = 1) -> dict:
    """Wall-clock: one fused ``memsim.system_speedup_population`` device call
    (base + D timing tables x all workloads, simulation + in-grid scoring)
    vs the retained per-request in-order reference walker
    (``memsim.reference.system_speedup_loop``) on the SAME hash-keyed traces
    and service rules — identical work, and the per-DIMM speedups must be
    literally bit-identical (integer latency totals + the shared jitted
    scorer)."""
    from repro.memsim import reference, sim

    tabs = np.array([[8.75, 23.75, 8.75, 6.25],
                     [11.25, 30.0, 11.25, 12.5],
                     [12.5, 32.5, 12.5, 13.75],
                     [10.0, 27.5, 10.0, 11.25]])[:n_dimms]
    kw = dict(n_requests=n_requests, scheduler="inorder")

    sim.system_speedup_population(tabs, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fused = sim.system_speedup_population(tabs, **kw)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        loop = reference.system_speedup_loop(tabs, **kw)
    t_loop = (time.perf_counter() - t0) / iters

    match = (np.array_equal(fused["per_dimm_workload_speedup"],
                            loop["per_dimm_workload_speedup"])
             and np.array_equal(fused["per_dimm_speedup"],
                                loop["per_dimm_speedup"]))
    return {"n_dimms": n_dimms, "n_requests": n_requests,
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def _operating_points():
    """A small N-axis grid spanning the four operating-point directions:
    nominal, a voltage step, a retention-stressed refresh/temperature point,
    and aggressive timings alone and combined with the new axes.  Every
    coordinate sits exactly on its axis quantization grid."""
    from repro.core.timing import OperatingPoint, TimingParams
    return [
        OperatingPoint(),
        OperatingPoint(vdd=1.10),
        OperatingPoint(refresh_ms=256.0, temp_C=75.0),
        OperatingPoint(timing=TimingParams(11.25, 30.0, 11.25, 12.5)),
        OperatingPoint(timing=TimingParams(10.0, 27.5, 10.0, 11.25),
                       vdd=1.25),
        OperatingPoint(timing=TimingParams(8.75, 25.0, 8.75, 10.0),
                       refresh_ms=128.0),
    ]


def operating_grid_speedup(n_dimms: int = 8, iters: int = 1) -> dict:
    """Wall-clock: one jitted N-axis ``operating_grid_arrays`` scan (every
    DIMM x every operating point, both error channels) vs the per-(DIMM,
    point) NumPy ``DimmModel.operating_point_eval`` loop on the SAME grid
    and the SAME ``op_point_key``-keyed Monte-Carlo decisions — identical
    work, pure batching + the grid lax.scan.  Decisions must match
    decision-for-decision; lambdas are float32 reductions (tolerance)."""
    from repro.core.geometry import TINY
    from repro.core.latency import worst_rows_internal
    from repro.core.population import make_population
    from repro.core.substrate import DimmBatch, operating_grid_arrays

    pop = make_population(TINY, n_dimms)
    batch = DimmBatch.from_population(pop)
    points = _operating_points()
    rows = worst_rows_internal(TINY)

    operating_grid_arrays(batch, points)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        grid = operating_grid_arrays(batch, points)
    t_batched = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        legacy = [[d.operating_point_eval(pt, rows) for pt in points]
                  for d in pop]
    t_loop = (time.perf_counter() - t0) / iters

    match = all(
        bool(grid["fails"][di, gi]) == legacy[di][gi][0]
        and np.allclose(grid["lam"][di, gi], legacy[di][gi][1],
                        rtol=2e-4, atol=1e-7)
        for di in range(len(pop)) for gi in range(len(points)))
    return {"n_dimms": len(pop), "n_points": len(points),
            "batched_ms": round(t_batched * 1e3, 1),
            "legacy_loop_ms": round(t_loop * 1e3, 1),
            "speedup": round(t_loop / max(t_batched, 1e-9), 1),
            "results_match": match}


def stream_profile_speedup(n_sizes: int = 10, chunk_size: int = 8,
                           seed: int = 3) -> dict:
    """Wall-clock: streamed chunked profiling of a STREAM of differently-
    sized synthetic fleets vs the dense per-fleet path.

    The dense population program re-lowers once per distinct fleet size D
    (a fresh XLA compile each); the streamed path clone-pads every chunk to
    ONE shape, so the chunk program compiles exactly once and serves every
    fleet — the fixed-compile half of ``core/streaming``'s contract (the
    fixed-memory half is the peak-RSS regression test).  Per-fleet tables
    must be BIT-identical, and the streamed pass must have lowered exactly
    one chunk program.
    """
    from repro.core import substrate
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import stream_profile_population
    from repro.core.substrate import profile_population_arrays

    sizes = (5, 6, 7, 9, 10, 11, 13, 14, 15, 17)[:n_sizes]
    fleets = [synthetic_fleet(n, TINY, seed=seed) for n in sizes]

    # compile accounting comes from the obs registry (the runtime metric the
    # one-compiled-program contract is now asserted on), cross-checked
    # against the cache dict it absorbed
    from repro import obs
    compiles = lambda: int(obs.REGISTRY.value(
        "repro_compile_programs_total", cache="chunk", entry="stream_profile"))
    jits_before = len(substrate._CHUNK_JIT_CACHE)
    c_before = compiles()
    t0 = time.perf_counter()
    streamed = [stream_profile_population(f, chunk_size=chunk_size,
                                          collect=True)["tables"]
                for f in fleets]
    t_stream = time.perf_counter() - t0
    new_jits = compiles() - c_before
    assert new_jits == len(substrate._CHUNK_JIT_CACHE) - jits_before, \
        "registry compile count disagrees with the chunk cache"

    t0 = time.perf_counter()
    dense = [np.asarray(profile_population_arrays(f.materialize()))
             for f in fleets]
    t_dense = time.perf_counter() - t0

    match = all(np.array_equal(s, d) for s, d in zip(streamed, dense))
    return {"n_fleets": len(sizes), "n_dimms_total": int(sum(sizes)),
            "chunk_size": chunk_size,
            "streamed_ms": round(t_stream * 1e3, 1),
            "dense_ms": round(t_dense * 1e3, 1),
            "speedup": round(t_dense / max(t_stream, 1e-9), 1),
            "chunk_programs_compiled": new_jits,
            "results_match": match}


def obs_overhead_smoke(n_dimms: int = 24, chunk_size: int = 8,
                       iters: int = 5) -> dict:
    """The observability-cost gate: the streamed chunk scan timed with the
    obs registry enabled vs disabled.

    There is no uninstrumented build to compare against, so the gate bounds
    what CAN differ: metrics enabled vs ``obs.disable()`` (every inc/observe
    an early return).  Because instrumentation lives strictly at host
    boundaries, the two runs must produce BIT-IDENTICAL tables, lower zero
    new chunk programs, and differ in wall time by < 2% (with an absolute
    floor — at smoke scale a scheduler hiccup is bigger than the handful of
    counter bumps per chunk).  Best-of-``iters`` timing on both sides.
    """
    from repro import obs
    from repro.core import substrate
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import stream_profile_population

    fleet = synthetic_fleet(n_dimms, TINY, seed=5)

    def run():
        return stream_profile_population(fleet, chunk_size=chunk_size,
                                         collect=True)["tables"]

    run()  # compile / warm the chunk program
    jits_before = len(substrate._CHUNK_JIT_CACHE)

    def best(f):
        ts, out = [], None
        for _ in range(iters):
            t0 = time.perf_counter()
            out = f()
            ts.append(time.perf_counter() - t0)
        return min(ts), out

    t_on, tables_on = best(run)
    obs.disable()
    try:
        t_off, tables_off = best(run)
    finally:
        obs.enable()

    overhead = (t_on - t_off) / max(t_off, 1e-9)
    return {"n_dimms": n_dimms, "chunk_size": chunk_size,
            "enabled_ms": round(t_on * 1e3, 2),
            "disabled_ms": round(t_off * 1e3, 2),
            "overhead_frac": round(overhead, 4),
            "abs_delta_ms": round((t_on - t_off) * 1e3, 2),
            "new_chunk_programs":
            len(substrate._CHUNK_JIT_CACHE) - jits_before,
            "results_match": bool(np.array_equal(tables_on, tables_off))}


SCRUB_RSS_CHILD = r"""
import sys
import numpy as np
from repro import obs
from repro.core.streaming import stream_secded_scrub

n_words, chunk, donate = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3] == "1"

def source(lo, hi):
    rng = np.random.default_rng(lo)
    return rng.integers(0, 2, (hi - lo, 72), dtype=np.int32)

out = stream_secded_scrub(source, n_words, chunk_size=chunk, donate=donate)
assert out["n_words"] == n_words
assert out["clean"] + out["corrected"] + out["uncorrectable"] == n_words
# obs.peak_rss_mb (VmHWM), NOT getrusage: ru_maxrss survives execve, so a
# child forked from a fat parent would report the PARENT's peak
peak_mb = obs.peak_rss_mb()
print(f"peak_rss_mb={peak_mb:.1f} donated={int(out['donated'])}")
"""


def scrub_rss_probe(n_words: int, chunk: int, donate: bool,
                    timeout: int = 900) -> float:
    """Peak RSS (MB) of a streamed SECDED scrub, measured in a CHILD process
    so the caller's allocations can't inflate the high-water mark (the
    ``RSS_SMOKE`` idiom from tests/test_streaming.py).  The donated and
    undonated children run the IDENTICAL program — only ``donate`` differs —
    so the pairwise delta isolates what buffer donation buys: with the
    corrected (N, 72) output aliasing the donated input, roughly one chunk
    buffer of peak RSS.  The child is pinned to the oracle route
    (``REPRO_FORCE_REF=1``): donation aliasing only pays on routes XLA
    compiles end to end, and a leg-inherited
    ``REPRO_BACKEND=cpu-pallas-interpret`` measures a ~0 delta (the
    interpreter stages buffers host-side), which is not a donation
    regression.  Used by the donation regression test and available to
    ad-hoc benching."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_FORCE_REF="1",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    env.pop("REPRO_NO_DONATE", None)
    env.pop("REPRO_BACKEND", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRUB_RSS_CHILD, str(n_words), str(chunk),
         "1" if donate else "0"],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"scrub rss probe failed (rc={proc.returncode}):\n"
                           f"{proc.stdout}{proc.stderr}")
    return float(proc.stdout.split("peak_rss_mb=")[1].split()[0])


def bench_streaming(n_dimms: int, chunk_size: int, budget_mb: int,
                    out_path: Path) -> dict:
    """The committed bench trajectory: profile + discover a synthetic fleet
    of ``n_dimms`` DIMMs through the streaming substrate in fixed memory,
    append the throughput record to ``BENCH_streaming.json``.

    Parity is asserted on a 64-DIMM prefix fleet against the dense path
    (bit-identical tables) before timing, and ``peak_rss_mb`` (the whole
    process, fleet synthesis included) must stay under ``budget_mb`` — the
    documented fixed-memory budget.
    """
    from repro.core.geometry import TINY
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import (stream_discover_generations,
                                      stream_profile_population)
    from repro.core.substrate import profile_population_arrays

    prefix = synthetic_fleet(64, TINY, seed=0)
    got = stream_profile_population(prefix, chunk_size=chunk_size,
                                    collect=True)["tables"]
    want = np.asarray(profile_population_arrays(prefix.materialize()))
    parity = bool(np.array_equal(got, want))
    if not parity:
        sys.exit("FAIL: streamed prefix tables != dense tables")

    fleet = synthetic_fleet(n_dimms, TINY, seed=0)
    t0 = time.perf_counter()
    prof = stream_profile_population(fleet, chunk_size=chunk_size)
    t_profile = time.perf_counter() - t0
    t0 = time.perf_counter()
    disc = stream_discover_generations(fleet, chunk_size=chunk_size,
                                       collect_labels=False)
    t_discover = time.perf_counter() - t0

    # the N-axis operating-point sweep rides the same streaming substrate:
    # a bounded prefix fleet (the grid multiplies per-DIMM cost by G, so the
    # sweep is budgeted independently of the headline fleet size)
    from repro.core.streaming import stream_operating_grid
    op_fleet = min(n_dimms, 2048)
    points = _operating_points()
    t0 = time.perf_counter()
    og = stream_operating_grid(synthetic_fleet(op_fleet, TINY, seed=0),
                               points, chunk_size=chunk_size)
    t_op = time.perf_counter() - t0
    op_fail_frac = np.asarray(og["fail_stats"]["mean"], np.float64)

    # the donation-aliased SECDED scrub rides the same chunk substrate: a
    # fixed-size word stream (independent of the headline fleet size), timed
    # for the throughput row of the trajectory
    from repro.core.streaming import stream_secded_scrub
    scrub_words, scrub_chunk = 1_048_576, 262_144

    def _scrub_source(lo, hi):
        rng = np.random.default_rng(lo)
        return rng.integers(0, 2, (hi - lo, 72), dtype=np.int32)

    t0 = time.perf_counter()
    scrub = stream_secded_scrub(_scrub_source, scrub_words,
                                chunk_size=scrub_chunk)
    t_scrub = time.perf_counter() - t0

    peak_mb = obs.peak_rss_mb()
    entry = {
        "date": time.strftime("%Y-%m-%d"),
        "backend": backend_tag(),
        "geometry": "TINY",
        "n_dimms": int(n_dimms),
        "chunk_size": int(prof["chunk_size"]),
        "n_chunks": int(prof["n_chunks"]),
        "profile_s": round(t_profile, 2),
        "profile_dimms_per_s": round(n_dimms / max(t_profile, 1e-9)),
        "discover_s": round(t_discover, 2),
        "discover_dimms_per_s": round(n_dimms / max(t_discover, 1e-9)),
        "n_generations": int(disc["n_generations"]),
        "op_grid_points": len(points),
        "op_fleet": int(op_fleet),
        "op_sweep_s": round(t_op, 2),
        "op_sweep_dimm_points_per_s": round(
            op_fleet * len(points) / max(t_op, 1e-9)),
        "op_fail_frac_max": round(float(op_fail_frac.max()), 4),
        "fastest_trcd_serial": int(prof["tables_min"]["serial"][0]),
        "scrub_words": int(scrub_words),
        "scrub_s": round(t_scrub, 2),
        "scrub_words_per_s": round(scrub_words / max(t_scrub, 1e-9)),
        "scrub_donated": bool(scrub["donated"]),
        "scrub_accounted": bool(scrub["clean"] + scrub["corrected"]
                                + scrub["uncorrectable"] == scrub_words),
        "budget_mb": int(budget_mb),
        "peak_rss_mb": round(peak_mb, 1),
        "prefix_parity": parity,
    }
    history = []
    if out_path.exists():
        history = json.loads(out_path.read_text())
    history.append(entry)
    out_path.write_text(json.dumps(history, indent=2) + "\n")
    print(json.dumps(entry, indent=2))
    if peak_mb > budget_mb:
        sys.exit(f"FAIL: peak RSS {peak_mb:.0f} MB exceeds the "
                 f"{budget_mb} MB budget")
    print(f"OK: {n_dimms} DIMMs profiled + discovered in "
          f"{peak_mb:.0f} MB (budget {budget_mb} MB), trajectory -> "
          f"{out_path}")
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="batched-vs-legacy-loop speedup gates only")
    ap.add_argument("--dimms", type=int, default=8)
    ap.add_argument("--bench-streaming", action="store_true",
                    help="fleet-scale streaming bench; appends to "
                         "BENCH_streaming.json")
    ap.add_argument("--bench-kernels", action="store_true",
                    help="per-backend kernel trajectory; appends one row per "
                         "(kernel, backend) to BENCH_kernels.json")
    ap.add_argument("--kernels-out",
                    default=str(Path(__file__).parent
                                / "BENCH_kernels.json"))
    ap.add_argument("--fleet", type=int, default=1_000_000,
                    help="fleet size for --bench-streaming")
    ap.add_argument("--chunk", type=int, default=4096,
                    help="chunk size for --bench-streaming")
    ap.add_argument("--budget-mb", type=int, default=4096,
                    help="peak-RSS budget for --bench-streaming")
    ap.add_argument("--out", default=str(Path(__file__).parent
                                         / "BENCH_streaming.json"))
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.bench_streaming:
        bench_streaming(args.fleet, args.chunk, args.budget_mb,
                        Path(args.out))
        return
    if args.bench_kernels:
        bench_kernels(Path(args.kernels_out))
        return
    if not args.smoke:
        # microbenchmark mode: report kernel timings, no gating
        tag = backend_tag()
        for k, v in kernels().items():
            print(f"kernel_{k},{v},backend={tag}")
        return
    s = profile_population_speedup(args.dimms)
    for k, v in s.items():
        print(f"profile_population_{k},{v}")
    if not s["results_match"]:
        sys.exit("FAIL: batched profile != legacy per-DIMM walker")
    if s["speedup"] < 5.0:
        sys.exit(f"FAIL: speedup {s['speedup']}x < 5x target")
    print(f"OK: profile_population {s['speedup']}x faster than legacy loop "
          f"on {s['n_dimms']} DIMMs")
    # the per-access loop is cheap enough to afford a bigger population here,
    # which amortizes the batched path's fixed dispatch overhead
    g = shuffling_gain_speedup(max(args.dimms, 16))
    for k, v in g.items():
        print(f"shuffling_gain_{k},{v}")
    if not g["results_match"]:
        sys.exit("FAIL: batched shuffling gain != per-access loop")
    if g["speedup"] < 5.0:
        sys.exit(f"FAIL: shuffling speedup {g['speedup']}x < 5x target")
    print(f"OK: shuffling_gain_population {g['speedup']}x faster than the "
          f"per-access loop on {g['n_dimms']} DIMMs")
    lt = lifetime_speedup()
    for k, v in lt.items():
        print(f"lifetime_{k},{v}")
    if not lt["results_match"]:
        sys.exit("FAIL: jitted lifetime scan != per-DIMM Python lifecycle")
    if lt["speedup"] < 5.0:
        sys.exit(f"FAIL: lifetime speedup {lt['speedup']}x < 5x target")
    print(f"OK: lifetime_population {lt['speedup']}x faster than the "
          f"Python lifecycle on {lt['n_dimms']} DIMMs x {lt['n_epochs']} "
          f"epochs")
    rm = recover_mapping_speedup(max(args.dimms, 24))
    for k, v in rm.items():
        print(f"recover_mapping_{k},{v}")
    if not rm["results_match"]:
        sys.exit("FAIL: batched scramble recovery != per-subarray loop "
                 "(decisions/confidences must be bit-identical)")
    if rm["speedup"] < 5.0:
        sys.exit(f"FAIL: recover speedup {rm['speedup']}x < 5x target")
    print(f"OK: recover_mapping_population {rm['speedup']}x faster than the "
          f"per-subarray loop on {rm['n_dimms']} DIMMs x "
          f"{rm['n_subarrays']} subarrays, bit-identical confidences")
    ms = memsim_grid_speedup()
    for k, v in ms.items():
        print(f"memsim_grid_{k},{v}")
    if not ms["results_match"]:
        sys.exit("FAIL: fused memsim grid != per-request in-order reference "
                 "(speedups must be bit-identical)")
    if ms["speedup"] < 5.0:
        sys.exit(f"FAIL: memsim speedup {ms['speedup']}x < 5x target")
    print(f"OK: memsim system_speedup_population {ms['speedup']}x faster "
          f"than the per-request reference walker on {ms['n_dimms']} tables, "
          f"bit-identical speedups")
    sp = stream_profile_speedup()
    for k, v in sp.items():
        print(f"stream_profile_{k},{v}")
    if not sp["results_match"]:
        sys.exit("FAIL: streamed chunked tables != dense tables "
                 "(must be bit-identical at any chunk size)")
    if sp["chunk_programs_compiled"] > 1:
        sys.exit(f"FAIL: streamed pass lowered "
                 f"{sp['chunk_programs_compiled']} chunk programs for "
                 f"{sp['n_fleets']} fleet sizes; the clone-padded chunk "
                 "must compile exactly once")
    if sp["speedup"] < 5.0:
        sys.exit(f"FAIL: streaming speedup {sp['speedup']}x < 5x target")
    print(f"OK: stream_profile_population {sp['speedup']}x faster than "
          f"dense per-size re-lowering over {sp['n_fleets']} fleet sizes, "
          f"one compiled chunk program, bit-identical tables")
    og = operating_grid_speedup(args.dimms)
    for k, v in og.items():
        print(f"operating_grid_{k},{v}")
    if not og["results_match"]:
        sys.exit("FAIL: batched N-axis operating grid != per-point NumPy "
                 "loop (decisions must match decision-for-decision)")
    if og["speedup"] < 5.0:
        sys.exit(f"FAIL: operating-grid speedup {og['speedup']}x < 5x target")
    print(f"OK: operating_grid_arrays {og['speedup']}x faster than the "
          f"per-(DIMM, point) loop on {og['n_dimms']} DIMMs x "
          f"{og['n_points']} operating points, matching decisions")
    ob = obs_overhead_smoke()
    for k, v in ob.items():
        print(f"obs_overhead_{k},{v}")
    if not ob["results_match"]:
        sys.exit("FAIL: obs enabled vs disabled changed the streamed tables "
                 "(instrumentation must be bitwise output-invariant)")
    if ob["new_chunk_programs"] != 0:
        sys.exit(f"FAIL: obs toggling lowered {ob['new_chunk_programs']} "
                 "new chunk programs; instrumentation must add zero compiles")
    if ob["overhead_frac"] >= 0.02 and ob["abs_delta_ms"] >= 2.0:
        sys.exit(f"FAIL: obs overhead {ob['overhead_frac']*100:.2f}% "
                 f"({ob['abs_delta_ms']}ms) over the disabled registry "
                 "exceeds the 2% gate")
    print(f"OK: obs overhead {ob['overhead_frac']*100:.2f}% on the streamed "
          f"chunk scan, bit-identical tables, zero new compiles")
    kr = kernel_route_smoke()
    for k, v in kr.items():
        print(f"kernel_route_{k},{v}")
    if not kr["results_match"]:
        sys.exit("FAIL: cpu-ref and cpu-pallas-interpret routes disagree "
                 "(integer kernels must be bit-identical across routes)")
    if kr["min_speedup"] < 5.0:
        sys.exit(f"FAIL: default-route speedup {kr['min_speedup']}x < 5x "
                 f"over forced interpret; the {kr['ref_tag']} default is "
                 "not earning its keep")
    print(f"OK: registry-dispatched {kr['ref_tag']} route "
          f"{kr['min_speedup']}x+ faster than forced {kr['interpret_tag']} "
          f"on 2 integer kernels, bit-identical outputs")


if __name__ == "__main__":
    main()
