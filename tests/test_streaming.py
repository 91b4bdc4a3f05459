"""Streaming population scans (core/streaming): bit-parity with the dense
substrate at multiple chunk sizes (including one that does not divide D),
online-reduction exactness contracts, the one-compiled-chunk-program rule,
packed error grids, the incremental generation clusterer, and the peak-RSS
regression that proves no dense population tensor is ever materialized."""
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import streaming as st
from repro.core import substrate
from repro.core.geometry import TINY
from repro.core.packing import (CountAccumulator, PackedBoolGrid,
                                narrow_counts, pack_bool, unpack_bool)
from repro.core.population import make_population, synthetic_fleet
from repro.core.substrate import (DimmBatch, fail_prob_grids,
                                  lifetime_population,
                                  profile_population_arrays,
                                  shuffling_gain_population)
from repro.core.timing import TimingParams
from repro.sharding import chunk_spans, dimm_mesh

D = 13
CHUNKS = (4, 5, 13)          # 4 and 5 do not divide 13; 13 is one chunk
FLEET = synthetic_fleet(D, TINY, seed=7)
BATCH = FLEET.materialize()

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="single-device runtime (use XLA_FLAGS="
           "--xla_force_host_platform_device_count=N)")


# ------------------------------------------------------------- chunk_spans

def test_chunk_spans_tile_exactly():
    for n, c in ((0, 4), (3, 4), (8, 4), (13, 4), (13, 13), (13, 100)):
        spans = chunk_spans(n, c)
        assert all(hi - lo <= c for lo, hi in spans)
        flat = [i for lo, hi in spans for i in range(lo, hi)]
        assert flat == list(range(n))


def test_chunk_spans_round_up_to_mesh():
    mesh = dimm_mesh(1)
    assert chunk_spans(10, 3, mesh) == chunk_spans(10, 3)


@multidevice
def test_chunk_spans_round_up_to_multidevice_mesh():
    mesh = dimm_mesh()
    n_dev = int(mesh.devices.size)
    spans = chunk_spans(5 * n_dev + 1, n_dev + 1, mesh)
    # chunk size rounded UP to a multiple of the device count: only the
    # final ragged span may be indivisible
    assert all((hi - lo) % n_dev == 0 for lo, hi in spans[:-1])


def test_chunk_spans_rejects_bad_sizes():
    with pytest.raises(ValueError):
        chunk_spans(-1, 4)
    with pytest.raises(ValueError):
        chunk_spans(4, 0)


# ----------------------------------------------------------------- packing

def test_narrow_counts_ladder():
    assert narrow_counts(np.array([0, 255])).dtype == np.uint8
    assert narrow_counts(np.array([0, 256])).dtype == np.uint16
    assert narrow_counts(np.array([0, 2 ** 16])).dtype == np.uint32
    assert narrow_counts(np.array([0, 2 ** 40])).dtype == np.int64
    with pytest.raises(ValueError):
        narrow_counts(np.array([-1, 5]))
    with pytest.raises(TypeError):
        narrow_counts(np.array([0.5, 1.0]))


def test_narrow_counts_roundtrip_exact():
    rng = np.random.default_rng(0)
    counts = rng.integers(0, 200, (4, 2, 16)).astype(np.int64)
    packed = narrow_counts(counts)
    assert packed.dtype == np.uint8
    np.testing.assert_array_equal(packed.astype(np.int64), counts)


def test_count_accumulator_widens_to_int64():
    acc = CountAccumulator()
    big = np.full((1, 3), 200, np.uint8)
    for _ in range(10 ** 3):
        acc.update(big)
    out = acc.result()
    assert out.dtype == np.int64
    assert int(out[0]) == 200 * 10 ** 3      # would wrap in uint8
    assert acc.n_seen == 10 ** 3
    with pytest.raises(TypeError):
        acc.update(np.ones((1, 3), np.float32))


def test_pack_bool_roundtrip():
    rng = np.random.default_rng(0)
    for shape in ((5, 64), (3, 7), (1, 13)):
        bits = rng.integers(0, 2, shape).astype(bool)
        packed = pack_bool(bits)
        assert packed.bits.dtype == np.uint8
        assert packed.nbytes < bits.size     # 8 cells/byte
        np.testing.assert_array_equal(unpack_bool(packed), bits)


def test_packed_bool_grid_is_packed():
    bits = np.zeros((4, 64), bool)
    bits[2, 5] = True
    g = pack_bool(bits)
    assert isinstance(g, PackedBoolGrid)
    assert g.shape == (4, 64)
    assert unpack_bool(g)[2, 5]
    with pytest.raises(TypeError):
        pack_bool(bits.astype(np.int8))


# ---------------------------------------------------- synthetic fleet / RNG

def test_synthetic_fleet_chunks_are_position_invariant():
    """Any chunk partition synthesizes identical DIMMs: leaves are pure
    functions of (seed, global serial), never chunk position."""
    whole = FLEET.chunk(0, D)
    parts = [FLEET.chunk(0, 5), FLEET.chunk(5, 13)]
    for leaf in substrate._LEAVES:
        got = np.concatenate([np.asarray(getattr(p, leaf)) for p in parts])
        np.testing.assert_array_equal(got, np.asarray(getattr(whole, leaf)),
                                      err_msg=leaf)


def test_fleet_dimm_model_matches_the_batched_sweep():
    """The NumPy walker on ``fleet_dimm_model`` reproduces the jitted
    population sweep table for table — the per-DIMM reference behind the
    chip smoke's check of served fleets."""
    from repro.core.population import fleet_dimm_model
    from repro.core.profiling import diva_profile_loop
    batch = FLEET.chunk(0, 3)
    dense = substrate.profile_population_arrays(
        batch, region="worst", temp_C=55.0, multibit_only=True)
    for i in range(3):
        tp = diva_profile_loop(fleet_dimm_model(batch, i), temp_C=55.0)
        np.testing.assert_array_equal(
            np.asarray([tp.trcd, tp.tras, tp.trp, tp.twr], np.float32),
            dense[i])


def test_stream_wrappers():
    s = st.as_stream(BATCH)
    assert isinstance(s, st.PopulationStream)
    assert s.n_dimms == D
    sub = s.chunk(3, 9)
    np.testing.assert_array_equal(np.asarray(sub.serial),
                                  np.asarray(BATCH.serial)[3:9])
    with pytest.raises(ValueError):
        s.chunk(5, 20)
    with pytest.raises(TypeError):
        st.as_stream([1, 2, 3])


# ------------------------------------------------------------- reductions

def test_welford_matches_numpy():
    rng = np.random.default_rng(1)
    data = rng.normal(0, 3, (50, 4))
    w = st.Welford()
    for lo in range(0, 50, 7):
        chunk = data[lo:lo + 7]
        w.update(chunk, np.arange(lo, lo + len(chunk)))
    out = w.result()
    np.testing.assert_allclose(out["mean"], data.mean(axis=0), rtol=1e-12)
    np.testing.assert_allclose(out["var"], data.var(axis=0), rtol=1e-12)
    assert out["count"] == 50


def test_min_ties_keep_earliest_serial():
    m = st.Min()
    m.update(np.array([[3.0], [1.0]]), np.array([10, 11]))
    m.update(np.array([[1.0], [2.0]]), np.array([12, 13]))  # ties the min
    out = m.result()
    assert out["value"][0] == 1.0 and out["serial"][0] == 11


def test_sum_exact_for_ints_rejects_mixed():
    s = st.Sum()
    s.update(np.full((4,), 2 ** 30, np.int32), np.arange(4))
    s.update(np.full((4,), 2 ** 30, np.int32), np.arange(4))
    assert int(s.result()) == 8 * 2 ** 30   # would overflow int32
    with pytest.raises(TypeError):
        s.update(np.ones(4, np.float32), np.arange(4))


# -------------------------------------------------- profile parity + compile

def _dense_tables():
    return np.asarray(profile_population_arrays(BATCH))


@pytest.mark.parametrize("chunk", CHUNKS)
def test_stream_profile_bit_parity(chunk):
    out = st.stream_profile_population(FLEET, chunk_size=chunk, collect=True)
    dense = _dense_tables()
    np.testing.assert_array_equal(out["tables"], dense)
    np.testing.assert_array_equal(out["tables_min"]["value"],
                                  dense.min(axis=0))
    np.testing.assert_array_equal(out["tables_max"]["value"],
                                  dense.max(axis=0))
    np.testing.assert_allclose(out["tables_stats"]["mean"],
                               dense.astype(np.float64).mean(axis=0),
                               rtol=1e-9)
    serials = np.asarray(BATCH.serial)
    np.testing.assert_array_equal(
        out["tables_min"]["serial"], serials[dense.argmin(axis=0)])


def test_stream_profile_one_compiled_chunk_program():
    """Fleets SMALLER than the chunk still pad to the full chunk width, so
    every fleet size reuses one compiled program (the regression that made
    the streamed path re-lower per small-fleet size, dense-style)."""
    key_count = lambda: len([k for k in substrate._CHUNK_JIT_CACHE
                             if k[0] == "stream_profile"])
    st.stream_profile_population(synthetic_fleet(3, TINY, seed=1),
                                 chunk_size=8)
    n0 = key_count()
    for n in (2, 5, 7, 9, 20):
        st.stream_profile_population(synthetic_fleet(n, TINY, seed=1),
                                     chunk_size=8)
    assert key_count() == n0


def test_stream_profile_from_resident_batch():
    out = st.stream_profile_population(BATCH, chunk_size=4, collect=True)
    np.testing.assert_array_equal(out["tables"], _dense_tables())


def test_stream_profile_rejects_per_dimm_regions_and_bad_banks():
    with pytest.raises(ValueError):
        st.stream_profile_population(FLEET, banks=3)


# ------------------------------------------------------------ lifetime parity

AGES = np.array([0.0, 2.0, 5.0], np.float32)
TEMPS = np.array([45.0, 55.0, 70.0])


@pytest.mark.parametrize("chunk", CHUNKS)
def test_stream_lifetime_bit_parity(chunk):
    dense = lifetime_population(BATCH, AGES, TEMPS)
    out = st.stream_lifetime_population(FLEET, AGES, TEMPS, chunk_size=chunk,
                                        collect=True)
    np.testing.assert_array_equal(
        out["timings"], np.moveaxis(np.asarray(dense["timings"]), 0, 1))
    np.testing.assert_array_equal(
        out["stale_fail"], np.moveaxis(np.asarray(dense["stale_fail"]), 0, 1))
    np.testing.assert_array_equal(
        out["stale_count"], np.asarray(dense["stale_fail"]).sum(axis=1))
    np.testing.assert_allclose(
        out["ecc_lambda_total"],
        np.asarray(dense["ecc_lambda"], np.float64).sum(axis=1), rtol=1e-6)


def test_stream_lifetime_rejects_per_dimm_schedules():
    with pytest.raises(ValueError):
        st.stream_lifetime_population(FLEET, np.zeros((3, D)), TEMPS)


# ----------------------------------------------------------- shuffling parity

def test_stream_shuffling_gain_sums_are_exact():
    from repro.core.shuffling import design_stripe_profiles
    probs = design_stripe_profiles(12)
    dense = shuffling_gain_population(probs, seeds=np.arange(12),
                                      n_accesses=300)
    # the dense API reports correctable counts as fractions; recover the
    # exact integers (small ints / small ints are exact in f64)
    denom = np.maximum(dense["total"], 1)
    c_ns = np.rint(dense["frac_no_shuffle"] * denom).astype(np.int64)
    c_s = np.rint(dense["frac_shuffle"] * denom).astype(np.int64)
    for chunk in (5, 12):
        out = st.stream_shuffling_gain(probs, chunk_size=chunk,
                                       n_accesses=300, collect=True)
        np.testing.assert_array_equal(out["total"], dense["total"])
        np.testing.assert_array_equal(out["corrected_no_shuffle"], c_ns)
        np.testing.assert_array_equal(out["corrected_shuffle"], c_s)
        for k in ("uncorrectable_no_shuffle", "undetected_shuffle"):
            np.testing.assert_array_equal(out[k],
                                          np.asarray(dense[k], np.int64))
            assert int(out[f"{k}_sum"]) == int(np.sum(dense[k]))
    fleet_frac = float(c_s.sum() / max(int(np.sum(dense["total"])), 1))
    assert out["frac_shuffle"] == pytest.approx(fleet_frac, rel=1e-12)


def test_stream_shuffling_gain_chunk_factory():
    from repro.core.shuffling import design_stripe_profiles
    probs = design_stripe_profiles(9)
    whole = st.stream_shuffling_gain(probs, chunk_size=4, n_accesses=200)
    fact = st.stream_shuffling_gain(lambda lo, hi: probs[lo:hi], n_dimms=9,
                                    chunk_size=3, n_accesses=200)
    assert whole["gain"] == fact["gain"]
    with pytest.raises(ValueError):
        st.stream_shuffling_gain(lambda lo, hi: probs[lo:hi], chunk_size=3)


# ------------------------------------------------------- error-summary parity

@pytest.mark.parametrize("chunk", (5, 13))
def test_stream_error_summary_parity(chunk):
    grids = np.asarray(fail_prob_grids(BATCH, "trp", 7.5, temp_C=85.0))
    out = st.stream_error_summary(FLEET, "trp", 7.5, chunk_size=chunk,
                                  collect_fail_maps=True)
    lam = grids.sum(axis=(1, 2, 3))
    np.testing.assert_allclose(out["lam_stats"]["mean"], lam.mean(),
                               rtol=1e-5)
    assert out["lam_min"]["serial"] == np.asarray(BATCH.serial)[lam.argmin()]
    np.testing.assert_allclose(out["grid_sum"],
                               grids.astype(np.float64).sum(axis=0),
                               rtol=1e-5)
    # hot_cells is an EXACT integer fold — chunk-invariant, bitwise
    np.testing.assert_array_equal(out["hot_cells"],
                                  (grids > 0.5).sum(axis=0).astype(np.int64))
    maps = np.concatenate([unpack_bool(p) for p in out["fail_maps"]])
    np.testing.assert_array_equal(maps, np.any(grids > 0.5, axis=(1, 3)))


# ---------------------------------------------------------- discovery parity

def test_streaming_generations_match_dense_clusterer():
    from repro.discovery.generation import cluster_generations
    from repro.discovery.signatures import (bit_signature_population,
                                            signature_features)
    counts = st.hash_poisson_counts(BATCH, "trp", 7.5, refresh_ms=256.0)
    sigs = bit_signature_population(counts.astype(np.int32))
    feats = signature_features(sigs)
    dense_labels = cluster_generations(feats)

    from repro.discovery.generation import StreamingGenerations
    for chunk in (4, 7, 13):
        gens = StreamingGenerations()
        parts = [gens.update(feats[lo:hi], counts[lo:hi])
                 for lo, hi in chunk_spans(D, chunk)]
        labels = gens.resolve_labels(np.concatenate(parts))
        np.testing.assert_array_equal(labels, dense_labels)
        assert gens.finalize()["n_generations"] == int(dense_labels.max()) + 1


def test_stream_discover_generations_chunk_invariant():
    outs = [st.stream_discover_generations(FLEET, chunk_size=c)
            for c in (4, 13)]
    np.testing.assert_array_equal(outs[0]["labels"], outs[1]["labels"])
    assert outs[0]["n_generations"] == outs[1]["n_generations"]
    for a, b in zip(outs[0]["canonical"], outs[1]["canonical"]):
        np.testing.assert_array_equal(a, b)    # exact integer-sum canonical


def test_hash_poisson_counts_chunk_invariant():
    whole = st.hash_poisson_counts(BATCH, "trp", 7.5)
    parts = np.concatenate(
        [st.hash_poisson_counts(FLEET.chunk(lo, hi), "trp", 7.5)
         for lo, hi in chunk_spans(D, 5)])
    np.testing.assert_array_equal(whole, parts)


# ------------------------------------------------- campaign row sums

def _grid_then_sum(monkeypatch):
    """Route the campaign's row sums through the ``fail_prob`` grid and
    XLA's sum over mats and columns (the formula the row-sum mode
    replaced), in programs traced afresh."""
    from repro.kernels import ops
    batch_fn = ops.fail_prob_batch

    def grids_summed(*args, reduce_rows=False, **kw):
        out = batch_fn(*args, **kw)
        return out.sum(axis=(1, 3)) if reduce_rows else out

    monkeypatch.setattr(ops, "fail_prob_batch", grids_summed)
    monkeypatch.setattr(substrate, "_CHUNK_JIT_CACHE", {})
    # a new partial object: jit's trace cache keys on the function, so
    # jitting ``_row_lambda_impl`` itself would replay an earlier trace
    monkeypatch.setattr(substrate, "_row_lambda_jit", jax.jit(
        functools.partial(substrate._row_lambda_impl),
        static_argnames=("pidx", "iters", "internal", "pallas")))


def test_campaign_row_sums_bit_identical_to_grid_then_sum(monkeypatch):
    """On the cpu-ref route the kernel's row-sum mode is the oracle's grid
    summed, so λ and the campaign's Poisson counts keep every bit."""
    kw = dict(refresh_ms=256.0)
    lam = substrate.row_error_lambda(BATCH, "trp", 7.5, **kw)
    lam_int = substrate.row_error_lambda(BATCH, "trcd", 10.0,
                                         internal_order=True)
    counts = st.hash_poisson_counts(BATCH, "trp", 7.5, seed=3, **kw)
    _grid_then_sum(monkeypatch)
    np.testing.assert_array_equal(
        lam, substrate.row_error_lambda(BATCH, "trp", 7.5, **kw))
    np.testing.assert_array_equal(
        lam_int, substrate.row_error_lambda(BATCH, "trcd", 10.0,
                                            internal_order=True))
    np.testing.assert_array_equal(
        counts, st.hash_poisson_counts(BATCH, "trp", 7.5, seed=3, **kw))


def _row_reduce_lowerings():
    from repro import obs
    from repro.kernels import ops
    return obs.REGISTRY.value("repro_kernel_row_reduce_total",
                              kernel="fail_prob", backend=ops.backend_tag())


def test_campaign_lowering_counts_a_row_sum(monkeypatch):
    """Lowering the campaign asks ``fail_prob`` for row sums, and the
    counter beside the kernel-trace counter shows it."""
    monkeypatch.setattr(substrate, "_CHUNK_JIT_CACHE", {})
    before = _row_reduce_lowerings()
    st.hash_poisson_counts(BATCH, "trp", 7.5, refresh_ms=256.0)
    assert _row_reduce_lowerings() > before


def test_fail_prob_grids_lower_the_grid_mode(monkeypatch):
    """Callers that need the cells keep the grid: ``fail_prob_grids``
    lowers the grid mode and leaves the row-sum counter alone."""
    from repro import obs
    from repro.kernels import ops
    monkeypatch.setattr(substrate, "_fail_prob_jit", jax.jit(
        functools.partial(substrate._fail_prob_impl),
        static_argnames=("cols", "pallas")))
    traces = lambda: obs.REGISTRY.value(  # noqa: E731
        "repro_kernel_traces_total", kernel="fail_prob",
        backend=ops.backend_tag())
    before, rows_before = traces(), _row_reduce_lowerings()
    grids = fail_prob_grids(BATCH, "trp", 7.5)
    g = BATCH.geom
    assert grids.shape == (D, g.mats_x, g.rows_per_mat, g.cols_per_mat)
    assert traces() > before
    assert _row_reduce_lowerings() == rows_before


def test_canonical_internal_profiles_mean_combine():
    """StreamingGenerations' exact integer sums reproduce the dense
    ``combine="mean"`` canonical bit for bit."""
    from repro.discovery.generation import (StreamingGenerations,
                                            canonical_internal_profiles)
    rng = np.random.default_rng(2)
    counts = rng.integers(0, 50, (6, 2, 16)).astype(np.int64)
    est = np.stack([np.stack([rng.permutation(16) for _ in range(2)])
                    for _ in range(6)])
    labels = np.array([0, 0, 1, 1, 1, 0])
    mean = canonical_internal_profiles(counts, est, labels, combine="mean")
    with pytest.raises(ValueError):
        canonical_internal_profiles(counts, est, labels, combine="mode")

    # streamed accumulation over two chunks, forcing the same labels by
    # feeding features whose leaders split exactly like `labels`
    feats = np.eye(2)[labels]                 # unit vectors per generation
    gens = StreamingGenerations()
    gens.update(feats[:4], counts[:4], est_ext_to_int=est[:4])
    gens.update(feats[4:], counts[4:], est_ext_to_int=est[4:])
    fin = gens.finalize()
    assert fin["n_generations"] == 2
    np.testing.assert_array_equal(np.stack(fin["canonical"]), mean)


# ------------------------------------------------------------- mesh parity

def _meshes():
    meshes = [dimm_mesh(1)]
    if jax.device_count() > 1:
        meshes.append(dimm_mesh())
    return meshes


def test_stream_profile_sharded_parity():
    dense = _dense_tables()
    for mesh in _meshes():
        out = st.stream_profile_population(FLEET, chunk_size=4, collect=True,
                                           mesh=mesh)
        np.testing.assert_array_equal(out["tables"], dense,
                                      err_msg=str(mesh))


@multidevice
def test_stream_error_summary_sharded_parity():
    ref = st.stream_error_summary(FLEET, "trp", 7.5, chunk_size=5)
    out = st.stream_error_summary(FLEET, "trp", 7.5, chunk_size=5,
                                  mesh=dimm_mesh())
    np.testing.assert_array_equal(out["hot_cells"], ref["hot_cells"])
    np.testing.assert_allclose(out["grid_sum"], ref["grid_sum"], rtol=1e-6)
    np.testing.assert_allclose(out["lam_stats"]["mean"],
                               ref["lam_stats"]["mean"], rtol=1e-6)


@multidevice
def test_stream_discover_sharded_parity():
    ref = st.stream_discover_generations(FLEET, chunk_size=5)
    out = st.stream_discover_generations(FLEET, chunk_size=5,
                                         mesh=dimm_mesh())
    np.testing.assert_array_equal(out["labels"], ref["labels"])


# -------------------------------------------------------- make_population

def test_stream_matches_dense_on_appendix_population():
    """The streamed path is not synthetic-fleet-only: a resident
    ``make_population`` batch streams to the same tables."""
    batch = DimmBatch.from_population(make_population(TINY, 7))
    out = st.stream_profile_population(batch, chunk_size=3, collect=True)
    np.testing.assert_array_equal(
        out["tables"], np.asarray(profile_population_arrays(batch)))


# -------------------------------------------------------- peak-RSS regression

RSS_SMOKE = r"""
import sys
from repro import obs
from repro.core.geometry import TINY
from repro.core.population import synthetic_fleet
from repro.core.streaming import stream_error_summary

n = 100_000
out = stream_error_summary(synthetic_fleet(n, TINY, seed=0), "trp", 7.5,
                           chunk_size=4096)
assert out["n_dimms"] == n and out["n_chunks"] == 25
peak_mb = obs.peak_rss_mb()
print(f"peak_rss_mb={peak_mb:.0f}")
sys.exit(0 if peak_mb < 2048 else 17)
"""


@pytest.mark.slow
def test_streamed_100k_smoke_stays_under_rss_budget():
    """100k TINY DIMMs through the streamed error summary must stay under
    2 GB peak RSS — the dense (D, mats, rows, cols) f32 grids alone would
    be ~6.5 GB (>7 GB with process overhead), so this fails if ANY step
    materializes a dense population tensor.  Measured in a subprocess via
    ``obs.peak_rss_mb`` (VmHWM): ``getrusage().ru_maxrss`` survives execve
    on Linux, so a child forked from a multi-GB mid-suite pytest parent
    reports the PARENT's high-water mark — that artifact is why this
    ceiling was historically ratcheted 2.5→3→4 GB; the child itself peaks
    ~0.7 GB, and the ceiling is back to ~3x that headroom."""
    env = dict(os.environ, REPRO_FORCE_REF="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    proc = subprocess.run([sys.executable, "-c", RSS_SMOKE], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"rss smoke failed (rc={proc.returncode}):\n{proc.stdout}{proc.stderr}"
    assert "peak_rss_mb=" in proc.stdout


@pytest.mark.slow
def test_scrub_donation_reduces_peak_rss():
    """Buffer donation must buy back real memory on the streamed SECDED
    scrub: with the (chunk, 72) i32 input donated to the same-shape scrubbed
    output, XLA reuses the buffer in place, so the no-donate child should
    peak at least ~half a chunk buffer (75.5 MB at 262144 words) above the
    donating child.  Measured in subprocesses via the same probe the
    ``--bench-streaming`` accounting uses, so allocator noise in THIS
    process can't fake a pass either way (the probe pins the children to
    the oracle route, so the delta is leg-independent)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.kernel_bench import scrub_rss_probe
    n_words, chunk = 4 * 262_144, 262_144
    donated_mb = scrub_rss_probe(n_words, chunk, donate=True)
    undonated_mb = scrub_rss_probe(n_words, chunk, donate=False)
    delta = undonated_mb - donated_mb
    assert delta > 35.0, (
        f"donation saved only {delta:.0f} MB (donate={donated_mb:.0f}, "
        f"no-donate={undonated_mb:.0f}); expected >= ~half the 75.5 MB "
        f"chunk buffer")
