"""Parity tests for the batched ECC/system evaluation layer: the jitted
shuffling pipeline vs the per-access NumPy loop, the lane-permutation kernels
vs core/shuffling's beat map, and the retrace-free ramlite simulator."""
import numpy as np
import pytest

from repro.core import ramlite, shuffling
from repro.core.substrate import (burst_bit_profile_population, burst_uniform,
                                  shuffling_gain_population)
from repro.core.timing import STANDARD, TimingParams


def _design_profiles(n_dimms: int, seed: int = 11) -> np.ndarray:
    """Fig 17-style profiles: a design-vulnerable burst stripe per DIMM."""
    return shuffling.design_stripe_profiles(n_dimms, seed=seed)


# ------------------------------------------------------------ hash sampling

def test_burst_uniform_numpy_jax_bit_identical():
    import jax.numpy as jnp
    acc = np.arange(16, dtype=np.uint32)[:, None]
    lane = np.arange(32, dtype=np.uint32)[None, :]
    seed = np.full((1, 1), 9, np.uint32)
    u_np = burst_uniform(seed, acc, lane, xp=np)
    u_jx = np.asarray(burst_uniform(jnp.asarray(seed), jnp.asarray(acc),
                                    jnp.asarray(lane), xp=jnp))
    np.testing.assert_array_equal(u_np, u_jx)
    assert (u_np >= 0).all() and (u_np < 1).all()
    # distinct queries give (essentially) distinct 24-bit draws; allow the
    # occasional birthday collision
    assert len(np.unique(u_np)) >= 16 * 32 - 2


# --------------------------------------------------- batched vs loop parity

def test_shuffling_gain_population_singleton_matches_loop():
    """The tentpole property on one DIMM: same seed, same counter-hash error
    draws, identical counts and fractions."""
    prob = _design_profiles(1)[0]
    loop = shuffling.shuffling_gain_loop(prob, n_accesses=300, seed=5)
    pop = shuffling_gain_population(prob[None], seeds=[5], n_accesses=300)
    assert int(pop["total"][0]) == loop["total"] > 0
    assert float(pop["frac_no_shuffle"][0]) == loop["frac_no_shuffle"]
    assert float(pop["frac_shuffle"][0]) == loop["frac_shuffle"]
    assert float(pop["gain"][0]) == loop["gain"]


def test_shuffling_gain_population_matches_loop_8dimms():
    """Bit-identical to the per-DIMM loop across >= 8 DIMMs in one call."""
    probs = _design_profiles(8)
    pop = shuffling_gain_population(probs, seeds=np.arange(8), n_accesses=200)
    for d in range(8):
        loop = shuffling.shuffling_gain_loop(probs[d], n_accesses=200, seed=d)
        assert int(pop["total"][d]) == loop["total"], d
        assert float(pop["frac_no_shuffle"][d]) == loop["frac_no_shuffle"], d
        assert float(pop["frac_shuffle"][d]) == loop["frac_shuffle"], d
    # uncorrectable accounting is per-codeword weight > 1
    uncorrectable = pop["uncorrectable_no_shuffle"]
    assert (uncorrectable >= pop["uncorrectable_shuffle"]).all()
    assert (pop["undetected_no_shuffle"] <= uncorrectable).all()


def test_shuffling_gain_wrapper_routes_through_population():
    prob = _design_profiles(1, seed=3)[0]
    wrap = shuffling.shuffling_gain(prob, n_accesses=250, seed=2)
    loop = shuffling.shuffling_gain_loop(prob, n_accesses=250, seed=2)
    assert wrap == {k: loop[k] for k in ("total", "frac_no_shuffle",
                                         "frac_shuffle", "gain")}


def test_shuffling_gain_population_force_ref_matches(monkeypatch):
    """REPRO_FORCE_REF=1 (pure-jnp oracles) == the Pallas interpret path.
    The dispatch mode is a static jit arg, so the env toggle retraces and the
    ref oracle genuinely runs (same shapes notwithstanding).  The baseline is
    pinned to the Pallas path so the toggle is exercised even when the whole
    session runs ref-forced (the jnp-oracles CI leg) — and, since the CPU
    default flipped to ``cpu-ref``, so the FORCE_REF call is a genuine
    static-arg flip (fresh trace through the oracle) rather than a jit cache
    hit on the very program the baseline already compiled."""
    from repro.core import substrate
    from repro.kernels import ref
    probs = _design_profiles(4, seed=7)
    monkeypatch.delenv("REPRO_FORCE_REF", raising=False)
    monkeypatch.setenv("REPRO_BACKEND", "cpu-pallas-interpret")
    pallas = shuffling_gain_population(probs, seeds=np.arange(4),
                                       n_accesses=111)
    calls = []
    orig = ref.diva_shuffle
    monkeypatch.setattr(ref, "diva_shuffle",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    oracle = shuffling_gain_population(probs, seeds=np.arange(4),
                                       n_accesses=111)
    assert calls, "REPRO_FORCE_REF=1 did not reach the jnp oracle"
    monkeypatch.delenv("REPRO_FORCE_REF")
    pallas2 = shuffling_gain_population(probs, seeds=np.arange(4),
                                        n_accesses=111)
    for k in pallas:
        np.testing.assert_array_equal(pallas[k], oracle[k])
        np.testing.assert_array_equal(pallas[k], pallas2[k])


def test_zero_probability_profile_is_all_clean():
    pop = shuffling_gain_population(np.zeros((2, 9, 64)), n_accesses=50)
    assert (pop["total"] == 0).all()
    assert (pop["frac_no_shuffle"] == 1.0).all()
    assert (pop["gain"] == 0.0).all()


# ----------------------------------------------------- lane-permutation map

def test_apply_shuffle_inverse_roundtrip_is_identity():
    from repro.kernels.shuffle import apply_shuffle
    rng = np.random.default_rng(0)
    b = rng.integers(0, 2, (40, 576)).astype(np.int32)
    for shuffle in (True, False):
        out = apply_shuffle(apply_shuffle(b, shuffle=shuffle, interpret=True),
                            inverse=True, shuffle=shuffle, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), b)


@pytest.mark.parametrize("shuffle", [True, False])
def test_apply_shuffle_matches_beat_of_bit_lane_for_lane(shuffle):
    """Kernel layout == core/shuffling's beat map: output lane
    beat*72 + chip*8 + dq holds input lane chip*64 + bit."""
    from repro.kernels.shuffle import apply_shuffle
    rng = np.random.default_rng(1)
    b = rng.integers(0, 2, (8, 576)).astype(np.int32)
    out = np.asarray(apply_shuffle(b, shuffle=shuffle, interpret=True))
    for chip in range(9):
        for bit in range(64):
            beat = int(shuffling.beat_of_bit(bit, chip, shuffle and chip < 8))
            dq = bit % shuffling.N_DQ
            np.testing.assert_array_equal(out[:, beat * 72 + chip * 8 + dq],
                                          b[:, chip * 64 + bit])


@pytest.mark.parametrize("shuffle", [True, False])
def test_assemble_error_masks_matches_kernel_layout(shuffle):
    """The per-access NumPy double loop and the permutation kernel build the
    same (8, 72) codeword masks."""
    from repro.kernels.shuffle import apply_shuffle
    rng = np.random.default_rng(2)
    e = (rng.random((9, 64)) < 0.05).astype(np.int32)
    masks = shuffling.assemble_error_masks(e, shuffle=shuffle)
    kern = np.asarray(apply_shuffle(e.reshape(1, 576), shuffle=shuffle,
                                    interpret=True)).reshape(8, 72)
    np.testing.assert_array_equal(masks, kern)


def test_codec_interleave_through_kernels_roundtrip():
    from repro.memsys import codec
    data = bytes(range(200)) * 2
    lanes = codec.protect_blob(data)
    out, stats = codec.recover_blob(lanes, len(data))
    assert out == data and stats.ok and stats.corrected == 0
    # a contiguous 7-bit run spreads over 7 distinct codewords -> corrected
    bad = codec.corrupt_run(lanes, burst=0, start_lane=101, n_bits=7)
    out, stats = codec.recover_blob(bad, len(data))
    assert out == data and stats.ok and stats.corrected == 7
    # codeword-major layout eats the same run in one word -> uncorrectable
    nl = codec.protect_blob(data, shuffle=False)
    bad = codec.corrupt_run(nl, burst=0, start_lane=4, n_bits=6)
    _, stats = codec.recover_blob(bad, len(data), shuffle=False)
    assert not stats.ok


# ------------------------------------------------- profiled-population chain

def test_burst_bit_profile_population_feeds_shuffling():
    from repro.core.geometry import SMALL
    from repro.core.population import make_population
    from repro.core.substrate import DimmBatch
    batch = DimmBatch.from_population(make_population(SMALL, 4))
    probs = burst_bit_profile_population(batch, "trp", 7.5, refresh_ms=256.0)
    assert probs.shape == (4, 9, 64)
    assert (probs >= 0).all() and (probs <= 1).all()
    # chips share the die design: per-chip profiles are strongly correlated
    c = np.corrcoef(probs[0, :8].reshape(8, -1))
    assert c[np.triu_indices(8, 1)].mean() > 0.9
    g = shuffling_gain_population(probs, seeds=batch.serial, n_accesses=100)
    # at these error rates individual DIMMs can lose (dense-error regime);
    # on average shuffling recovers errors (Fig 17)
    assert float(np.mean(g["gain"])) > 0


# ------------------------------------------------------------ ramlite fixes

def test_make_trace_achieved_hit_rate_matches_spec():
    """Bugfix: intended hits target the bank's most recently opened row, so
    the simulator's measured row-hit rate tracks the workload spec."""
    for w in ramlite.WORKLOADS[:4]:
        tr = ramlite.make_trace(w, 8000, 16, seed=0)
        res = ramlite.simulate_trace(tr, STANDARD)
        assert abs(res["row_hit_rate"] - w.row_hit_rate) < 0.02, w.name


def test_write_completion_excludes_twr():
    """Bugfix: tWR is write recovery — it must not appear in the write's own
    completion latency (which is tCWL-based)."""
    t = STANDARD
    tc = ramlite.timing_cycles(t)
    tr = {"bank": np.zeros(1, np.int32), "row": np.ones(1, np.int32),
          "write": np.ones(1, np.int32), "arrive": np.zeros(1, np.int32)}
    r = ramlite.simulate_trace(tr, t, banks=2)
    assert r["avg_latency_cycles"] == float(tc[2] + tc[0] + tc[5])  # tRP+tRCD+tCWL
    # and it is invariant under tWR changes
    r2 = ramlite.simulate_trace(tr, t.replace(twr=5.0), banks=2)
    assert r2["avg_latency_cycles"] == r["avg_latency_cycles"]


def test_twr_delays_next_precharge_by_bank_occupancy():
    """tWR reaches throughput through the bank's precharge-ready time: a miss
    right after a write pays the write recovery (when tRAS is not binding)."""
    t = STANDARD.replace(tras=15.0)
    tr = {"bank": np.zeros(2, np.int32), "row": np.array([1, 2], np.int32),
          "write": np.array([1, 0], np.int32),
          "arrive": np.zeros(2, np.int32)}
    hi = ramlite.simulate_trace(tr, t, banks=2)
    lo = ramlite.simulate_trace(tr, t.replace(twr=5.0), banks=2)
    delta = (hi["avg_latency_cycles"] - lo["avg_latency_cycles"]) * 2
    assert delta == t.cycles("twr") - t.replace(twr=5.0).cycles("twr")


def test_simulate_trace_does_not_retrace_on_timing_values():
    """The retrace-free contract: TimingParams enter as traced cycle arrays,
    so a timing sweep reuses the compiled program."""
    tr = ramlite.make_trace(ramlite.WORKLOADS[3], 500, 16, seed=1)
    base = ramlite.simulate_trace(tr, STANDARD)  # warm the cache
    n0 = ramlite.N_TRACES
    grid = [TimingParams(trcd=13.75 - 1.25 * k, tras=35.0 - 2.5 * k,
                         trp=13.75 - 1.25 * k, twr=15.0 - 1.25 * k)
            for k in range(4)]
    lats = [ramlite.simulate_trace(tr, t)["avg_latency_cycles"] for t in grid]
    assert ramlite.N_TRACES == n0
    assert lats[0] == base["avg_latency_cycles"]
    assert lats[-1] < lats[0]  # values really flow through the traced operand


def test_system_speedup_population_singleton_matches_summary():
    fast = TimingParams(trcd=8.75, tras=23.75, trp=8.75, twr=6.25)
    s = ramlite.speedup_summary(fast, STANDARD, n_requests=2000)
    pop = ramlite.system_speedup_population([fast], n_requests=2000)
    assert pop["per_dimm_speedup"].shape == (1,)
    assert pop["per_dimm_speedup"][0] == pytest.approx(
        s["mean_singlecore_speedup"], abs=1e-12)
    # (D, 4) ns-array input is accepted too
    pop2 = ramlite.system_speedup_population(
        np.asarray([[8.75, 23.75, 8.75, 6.25]]), n_requests=2000)
    assert pop2["per_dimm_speedup"][0] == pop["per_dimm_speedup"][0]


def test_system_speedup_population_profiled_dimms():
    """Fig 19 chain: profiled timings for several DIMMs -> per-DIMM speedups
    in one device call; every profiled DIMM speeds up."""
    from repro.core.geometry import SMALL
    from repro.core.population import make_population
    from repro.core.substrate import DimmBatch, profile_population
    pop = make_population(SMALL, 6)
    tps = profile_population(DimmBatch.from_population(pop), temp_C=85.0,
                             multibit_only=True)
    s = ramlite.system_speedup_population(tps, n_requests=2000)
    assert s["per_dimm_speedup"].shape == (6,)
    assert (s["per_dimm_speedup"] > 1.0).all()
    assert s["min_speedup"] <= s["median_speedup"] <= s["max_speedup"]
