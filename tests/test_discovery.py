"""Blind-discovery subsystem coverage (layer 3d).

Parity: the jitted ``recover_mapping_population`` must match the per-subarray
NumPy reference (``mapping.estimate_row_mapping`` via
``recover_mapping_loop``) decision-for-decision AND confidence-bit-for-bit;
the bit-signature kernel triple must agree value-for-value; every new entry
point must be bit-identical under a DIMM-axis mesh.  Recovery: random
permutation+XOR scrambles are recovered exactly at zero noise for every
supported row width (hypothesis property, when installed).  End to end:
``BlindDiva`` (no geometry metadata) reaches the geometry-oracle
``diva_profile`` timing tables on >= 95% of a 32-DIMM population at the
default noise level.
"""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.geometry import SMALL, RowScramble, vendor_scramble
from repro.core.mapping import (_bit_signature, _signature_sums,
                                estimate_row_mapping, mapping_confidences)
from repro.core.population import make_population
from repro.core.substrate import DimmBatch, profile_population_arrays
from repro.discovery import (BlindDiva, bit_signature_population,
                             cluster_generations, recover_mapping_loop,
                             recover_mapping_population, signature_features,
                             vote_mapping)
from repro.discovery.blind import blind_vs_oracle, campaign_counts
from repro.discovery.generation import (canonical_internal_profiles,
                                        covers_both_arms, onset_profile,
                                        vulnerable_rows)
from repro.discovery.recover import mapping_tables
from repro.sharding import dimm_mesh

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: property tests skip,
    HAVE_HYPOTHESIS = False  # everything else still runs

R = SMALL.rows_per_mat
NBITS = int(np.log2(R))

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="single-device runtime (use XLA_FLAGS="
           "--xla_force_host_platform_device_count=N)")


@pytest.fixture(scope="module")
def campaign():
    """A small population's discovery campaign (counts + expectations)."""
    pop = make_population(SMALL, 6)
    batch = DimmBatch.from_population(pop)
    counts, expected = campaign_counts(pop, batch)
    return pop, batch, counts, expected


def _meshes():
    meshes = [dimm_mesh(1)]
    if jax.device_count() > 1:
        meshes.append(dimm_mesh())
    return meshes


# ----------------------------------------------------- bit-signature kernel

def test_bit_signature_triple_agrees():
    """Pallas kernel == jnp oracle == NumPy reference, value for value (the
    reduction is exact integer arithmetic; no float tolerance needed)."""
    from repro.kernels import ops, ref
    from repro.kernels.bit_signature import bit_signature as bs_pallas
    rng = np.random.default_rng(7)
    counts = rng.integers(0, 2 ** 20, (9, R)).astype(np.int32)
    k = np.asarray(bs_pallas(counts, nbits=NBITS, interpret=True))
    o = np.asarray(ref.bit_signature(counts, NBITS))
    m = np.stack([_signature_sums(row, NBITS) for row in counts])
    np.testing.assert_array_equal(k, o)
    np.testing.assert_array_equal(k, m.astype(np.int32))
    d = np.asarray(ops.bit_signature(counts, nbits=NBITS))
    np.testing.assert_array_equal(k, d)


def test_bit_signature_population_matches_mapping_reference(campaign):
    _, _, counts, _ = campaign
    summed = counts.sum(axis=0)
    sigs = bit_signature_population(summed)
    D, S = summed.shape[:2]
    for d in range(D):
        for s in range(S):
            np.testing.assert_array_equal(
                sigs[d, s], _bit_signature(summed[d, s], NBITS))


def test_bit_signature_population_sharded_parity(campaign):
    _, _, counts, _ = campaign
    summed = counts.sum(axis=0)
    ref = bit_signature_population(summed)
    for mesh in _meshes():
        out = bit_signature_population(summed, mesh=mesh)
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


# ----------------------------------------------- batched recovery vs loop

def test_recover_population_matches_loop_bitwise(campaign):
    """The jitted program and the per-subarray reference: decisions AND
    confidences literally equal (the integer-votes + host-division parity
    construction)."""
    _, _, counts, expected = campaign
    rec = recover_mapping_population(counts[1], expected[1])
    loop = recover_mapping_loop(counts[1], expected[1])
    for key in ("ext_bit", "xor", "confidence", "n_significant_pairs",
                "est_ext_to_int"):
        np.testing.assert_array_equal(rec[key], loop[key], err_msg=key)


def test_recover_population_sharded_parity(campaign):
    _, _, counts, expected = campaign
    ref = recover_mapping_population(counts[1], expected[1])
    for mesh in _meshes():
        out = recover_mapping_population(counts[1], expected[1], mesh=mesh)
        for key in ref:
            np.testing.assert_array_equal(ref[key], out[key],
                                          err_msg=f"{key} on {mesh}")


@multidevice
def test_recover_population_sharded_parity_with_padding(campaign):
    _, _, counts, expected = campaign
    n = jax.device_count()
    ref = recover_mapping_population(counts[1, :n + 1], expected[1, :n + 1])
    out = recover_mapping_population(counts[1, :n + 1], expected[1, :n + 1],
                                     mesh=dimm_mesh())
    for key in ref:
        np.testing.assert_array_equal(ref[key], out[key], err_msg=key)


def test_recover_rejects_float_counts():
    with pytest.raises(ValueError, match="integer"):
        recover_mapping_population(np.ones((1, 1, R)), np.ones(R))


# ------------------------------------------------- mapping.py satellite fix

def test_zero_signature_pins_xor_to_zero():
    """Constant observed counts: every signature is exactly zero, so every
    XOR bit must be 0 (np.sign's 0 used to infer xor=1 spuriously) and the
    (tied) magnitude ordering must be deterministic: stable == bit order."""
    expected = np.arange(R, dtype=np.float64) * 1000.0
    res = estimate_row_mapping(np.full(R, 7, np.int64), expected)
    assert all(r["xor"] == 0 for r in res)
    # stable tie-break: rank slots fill in bit order on the observed side
    order_int = np.argsort(-np.abs(_signature_sums(expected, NBITS)),
                           kind="stable")
    for rank, i in enumerate(order_int):
        assert res[i]["ext_bit"] == rank


def test_integer_and_float_counts_agree_on_decisions():
    """The exact-integer route and the float64 route rank and sign the same
    clean profile identically."""
    sc = vendor_scramble("synthetic", NBITS, 5)
    expected = (np.arange(R, dtype=np.float64) + 1.0) * 1000.0
    counts = expected[sc.ext_to_int(np.arange(R))]
    res_f = estimate_row_mapping(counts, expected)
    res_i = estimate_row_mapping(counts.astype(np.int64), expected)
    assert [r["ext_bit"] for r in res_f] == [r["ext_bit"] for r in res_i]
    assert [r["xor"] for r in res_f] == [r["xor"] for r in res_i]
    assert tuple(r["ext_bit"] for r in res_i) == sc.perm
    for r in res_i:
        assert r["xor"] == (sc.xor_mask >> r["int_bit"]) & 1


# ------------------------------------------------------- exact recovery

def _linear_profile(nbits: int) -> np.ndarray:
    """Integer design profile with distinct, nonzero per-bit signatures
    (signature of bit b = 1000 * 2^b): recovery is well-posed at any width."""
    return (np.arange(2 ** nbits, dtype=np.int64) + 1) * 1000


@pytest.mark.parametrize("nbits", [2, 3, 5, NBITS])
@pytest.mark.parametrize("seed", [0, 3, 11])
def test_recover_exact_known_scramble_noise_free(nbits, seed):
    n = 2 ** nbits
    sc = vendor_scramble("synthetic", nbits, seed)
    profile = _linear_profile(nbits)
    counts = profile[sc.ext_to_int(np.arange(n))]
    rec = recover_mapping_population(counts[None, None, :],
                                     profile.astype(np.float64))
    assert tuple(int(b) for b in rec["ext_bit"][0, 0]) == sc.perm
    for i in range(nbits):
        assert rec["xor"][0, 0, i] == (sc.xor_mask >> i) & 1
    np.testing.assert_array_equal(rec["est_ext_to_int"][0, 0],
                                  sc.ext_to_int(np.arange(n)))


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(data=st.data(), nbits=st.integers(2, NBITS))
    def test_recover_exact_random_scramble_property(data, nbits):
        """Hypothesis: ANY permutation + XOR mask at ANY supported row width
        is recovered exactly from noise-free counts — batched program and
        NumPy reference alike."""
        n = 2 ** nbits
        perm = tuple(data.draw(st.permutations(range(nbits))))
        mask = data.draw(st.integers(0, n - 1))
        sc = RowScramble(perm, mask)
        profile = _linear_profile(nbits)
        counts = profile[sc.ext_to_int(np.arange(n))]
        rec = recover_mapping_population(counts[None, None, :],
                                         profile.astype(np.float64))
        assert tuple(int(b) for b in rec["ext_bit"][0, 0]) == perm
        assert all(int(rec["xor"][0, 0, i]) == (mask >> i) & 1
                   for i in range(nbits))
        res = estimate_row_mapping(counts, profile.astype(np.float64))
        assert tuple(r["ext_bit"] for r in res) == perm
        np.testing.assert_array_equal(
            rec["confidence"][0, 0], mapping_confidences(res))


# ------------------------------------------------------------- voting

def test_vote_mapping_majority_and_permutation():
    order = np.array([2, 1, 0])
    ext = np.array([[0, 1, 2], [0, 1, 2], [1, 0, 2], [2, 1, 0]])
    xor = np.array([[0, 1, 0], [0, 1, 0], [1, 0, 0], [0, 0, 1]])
    conf = np.ones((4, 3))
    b, x = vote_mapping(ext, xor, conf, order)
    assert sorted(b.tolist()) == [0, 1, 2]         # stays a permutation
    np.testing.assert_array_equal(b, [0, 1, 2])    # the 2-vote majority
    np.testing.assert_array_equal(x, [0, 1, 0])
    est, i2e = mapping_tables(b, x, 8)
    np.testing.assert_array_equal(np.sort(est), np.arange(8))  # bijection
    np.testing.assert_array_equal(est[i2e], np.arange(8))


# ------------------------------------------------ generations and regions

def test_vulnerable_rows_covers_both_arms_and_plateaus():
    # open-bitline V with a monotone tilt: plain top-2 would take {127, 126}
    r = np.arange(R, dtype=np.float64)
    v_shape = np.maximum(r, (R - 1) - r) ** 4 / (R - 1) ** 4 * 1e5 + r * 10
    np.testing.assert_array_equal(vulnerable_rows(v_shape, 2), [0, R - 1])
    # saturated plateau at the top arm: the pick snaps to the address edge
    sat = v_shape.copy()
    sat[R - 8:] = sat[R - 8]
    np.testing.assert_array_equal(vulnerable_rows(sat, 2), [0, R - 1])
    # onset selection: first profile with real signal wins
    quiet = np.zeros(R)
    np.testing.assert_array_equal(
        onset_profile(np.stack([quiet, v_shape, quiet ** 0]), 32.0), v_shape)
    np.testing.assert_array_equal(
        onset_profile(np.stack([quiet, quiet]), 32.0), quiet)


def test_vulnerable_rows_never_duplicates_on_shared_plateau():
    """Two separated picks whose plateaus touch the same address edge must
    not both snap there: the second keeps its own row (a duplicated pick
    would silently halve the test region)."""
    n = 64
    profile = np.full(n, 1000.0)
    profile[10] = 1002.0
    profile[40] = 1001.0
    profile[63] = 0.0          # plateau reaches row 0 but not row n-1
    rows = vulnerable_rows(profile, 2)
    assert len(set(rows.tolist())) == 2, rows
    # row 63's zero tilts the least-squares line down, so the detrended
    # ranking puts row 62 first (snapped to row 0) and row 61 second, whose
    # plateau snaps to row 0 too: it keeps its own row
    np.testing.assert_array_equal(rows, [0, 61])


# The onset profiles of the 12 generations that the first 64-DIMM chunk of
# synthetic_fleet(128, FULL, seed=0) founds under FleetConfig(chunk_size=64)
# (the canonical profile each founding reads its test rows off), with the
# founders' design (serial mod 11; A-T founds two generations of 3), and the
# rows and verification of the raw-count greedy rule that preceded
# detrending.  B-F, B-M and C-F are the low-variation dies (die scale
# 0.15-0.22), whose faint V the row-index tilt hid: that rule gave them
# rows [383, 511].
with np.load(Path(__file__).parent / "data"
             / "onset_profiles_full_seed0.npz") as _f:
    ONSET = dict(_f)
FAINT = ("B-F", "B-M", "C-F")


@pytest.mark.parametrize("g", range(len(ONSET["design"])),
                         ids=[f"{g}-{d}" for g, d in enumerate(ONSET["design"])])
def test_onset_profile_regions_cover_both_arms_at_full_geometry(g):
    profile, design = ONSET["profile"][g], str(ONSET["design"][g])
    R_full = profile.shape[0]
    rows = vulnerable_rows(profile, 2)
    # every region shows both arms, at the mat's two edge rows: the
    # design-worst rows of the open-bitline V
    assert covers_both_arms(profile, rows)
    np.testing.assert_array_equal(rows, [0, R_full - 1])
    if design in FAINT:
        np.testing.assert_array_equal(ONSET["raw_greedy_rows"][g], [383, 511])
        assert not covers_both_arms(profile, ONSET["raw_greedy_rows"][g])
    else:                      # the strong designs keep the rows they had
        np.testing.assert_array_equal(rows, ONSET["raw_greedy_rows"][g])


def _raw_greedy(profile, k=2):
    """The rule before detrending: greedy by raw count, R // (2k) apart
    (the synthetic profiles below have no plateau to snap)."""
    picked = []
    for r in np.argsort(-profile, kind="stable"):
        if all(abs(int(r) - p) >= len(profile) // (2 * k) for p in picked):
            picked.append(int(r))
        if len(picked) == k:
            break
    return np.sort(picked)


_RR = np.arange(512, dtype=np.float64)
_V4 = (np.maximum(_RR, 511 - _RR) / 511) ** 4      # open-bitline V, 1 at edges
TILTS = {"exponential": 50.0 * np.exp(_RR / 40.0),  # counts of a steep tail
         "linear": 100.0 + 1e4 * _RR}
ARMS = {"exponential": 5e3, "linear": 1e5}         # a faint V on each tilt


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("v", ["faint_v", "no_v"])
@pytest.mark.parametrize("tilt", sorted(TILTS))
def test_tilted_profiles_give_both_arms_or_one_arm(tilt, v, seed):
    """A monotone row-index tilt, a faint V or none, and Poisson noise at
    R = 512: the raw greedy rule lands on the tilt ([383, 511]); the
    detrended rule finds both edge rows, or reports one arm (which
    ``FleetServer`` never verifies)."""
    lam = TILTS[tilt] + (ARMS[tilt] * _V4 if v == "faint_v" else 0.0)
    profile = np.random.default_rng(seed).poisson(lam)
    np.testing.assert_array_equal(_raw_greedy(profile), [383, 511])
    assert not covers_both_arms(profile, [383, 511])
    rows = vulnerable_rows(profile, 2)
    if covers_both_arms(profile, rows):
        np.testing.assert_array_equal(rows, [0, 511])
    # a faint V always shows; so does the convex exponential tilt once the
    # line is out, while a linear tilt without a V leaves noise alone
    assert covers_both_arms(profile, rows) == (v == "faint_v"
                                               or tilt == "exponential")


def test_covers_both_arms_needs_an_arm_wider_than_one_row():
    flat = np.full(64, 1000)
    assert not covers_both_arms(flat, [0, 63])
    spikes = flat.copy()
    spikes[[0, 63]] = 2000     # two one-row excursions, a dip between
    spikes[30] = 0
    assert not covers_both_arms(spikes, [0, 63])
    spikes[[1, 62]] = 2000     # arms two rows wide
    assert covers_both_arms(spikes, [0, 63])
    assert not covers_both_arms(spikes, [0])     # one row, one arm at most


def test_generation_clustering_groups_same_die(campaign):
    pop, _, counts, _ = campaign
    sigs = bit_signature_population(counts.sum(axis=0))
    labels = cluster_generations(signature_features(sigs), threshold=0.85)
    dies = [d.vendor.name + d.vendor.die for d in pop]
    strong = [i for i, die in enumerate(dies)
              if "F" not in die and "M" not in die]
    for i in strong:
        for j in strong:
            if dies[i] == dies[j]:
                assert labels[i] == labels[j], (i, j, dies[i])
            else:
                assert labels[i] != labels[j], (i, j, dies[i], dies[j])


def test_canonical_profile_recovers_design_order():
    """Scattering scrambled counts back through the true mapping re-exposes
    the design profile — and the median kills a one-subarray repair spike."""
    sc = vendor_scramble("synthetic", NBITS, 4)
    profile = _linear_profile(NBITS).astype(np.float64)
    ext = profile[sc.ext_to_int(np.arange(R))]
    counts = np.tile(ext, (1, 4, 1))
    counts[0, 2, 5] = 10 * profile.max()   # a repaired-row artifact
    est = np.tile(sc.ext_to_int(np.arange(R)), (1, 4, 1))
    canon = canonical_internal_profiles(counts, est, np.zeros(1, np.int64))
    np.testing.assert_array_equal(canon[0], profile)


# --------------------------------------------------- end-to-end BlindDiva

def test_blind_diva_matches_oracle_on_population():
    """The acceptance gate: BlindDiva — no geometry metadata — reaches the
    geometry-oracle diva_profile timing table on >= 95% of a 32-DIMM
    population at the default noise level."""
    pop = make_population(SMALL, 32)
    batch = DimmBatch.from_population(pop)
    counts, expected = campaign_counts(pop, batch)
    disc = BlindDiva().discover(counts, expected, serials=batch.serial)
    out = blind_vs_oracle(batch, disc, temp_C=55.0, multibit_only=True)
    assert out["n_dimms"] == 32
    assert out["agreement"] >= 0.95, out["agreement"]
    # the cross-DIMM consistency artifact: every strong-signal DIMM's voted
    # mapping equals its true vendor scramble
    truth = np.stack([d.vendor.scramble.ext_to_int(np.arange(R))
                      for d in pop])
    strong = [i for i, d in enumerate(pop)
              if d.vendor.die not in ("F", "M")]
    exact = sum(np.array_equal(disc.ext_to_int[i], truth[i]) for i in strong)
    assert exact >= 0.95 * len(strong), (exact, len(strong))
    # the discovered region really is DIVA's: most DIMMs' external test rows
    # decode to the true design-worst internal rows
    assert out["region_recovered_frac"] >= 0.6
    # cost story: both DIVA modes test 2 rows against 512 for conventional
    assert out["rows_tested_blind"] == out["rows_tested_oracle"] == 2
    assert out["rows_tested_conventional"] == R * SMALL.subarrays


def test_blind_region_profile_is_bit_identical_when_region_matches(campaign):
    """The profiling hash never keys on the region, so a per-DIMM region
    naming the worst rows reproduces region='worst' bit for bit — sharded
    and unsharded."""
    _, batch, _, _ = campaign
    D = batch.n_dimms
    rows = np.tile([0, R - 1], (D, 1))
    ref = profile_population_arrays(batch, temp_C=55.0, multibit_only=True)
    out = profile_population_arrays(batch, region=rows, temp_C=55.0,
                                    multibit_only=True)
    np.testing.assert_array_equal(ref, out)
    for mesh in _meshes():
        sharded = profile_population_arrays(batch, region=rows, temp_C=55.0,
                                            multibit_only=True, mesh=mesh)
        np.testing.assert_array_equal(ref, sharded, err_msg=str(mesh))


def test_diva_profiler_discovery_mode(campaign):
    """DivaProfiler(discovery=...) profiles the discovered EXTERNAL rows —
    when they decode to the worst region, the served table matches the
    geometry-oracle profiler exactly."""
    from repro.core.profiling import DivaProfiler
    pop, _, _, _ = campaign
    dimm = pop[0]
    ext = dimm.vendor.scramble.int_to_ext(np.array([0, R - 1]))
    oracle = DivaProfiler(dimm).timing()
    blind = DivaProfiler(dimm, discovery=np.asarray(ext)).timing()
    assert blind == oracle


# --------------------------------------------- straggler satellite fix
# (runtime/straggler.py rides along in this PR; test_substrates.py is
# hypothesis-gated, so the fix is covered here)

def test_cluster_probe_sees_injected_straggler():
    from repro.runtime.straggler import CanaryProber, ClusterSim
    sim = ClusterSim(n_pods=2, devices_per_pod=64, stragglers={10: 30.0},
                     seed=3)
    healthy = ClusterSim(n_pods=2, devices_per_pod=64, seed=3)
    assert sim.probe(10) - healthy.probe(10) == pytest.approx(30.0, abs=3.0)
    # a straggling canary device now inflates the timeout instead of
    # reading healthy
    worst = sim.worst_path_device()
    slow = ClusterSim(n_pods=2, devices_per_pod=64,
                      stragglers={worst: 30.0}, seed=5)
    fast = ClusterSim(n_pods=2, devices_per_pod=64, seed=5)
    t_slow = CanaryProber(slow, period=50).maybe_reprobe()
    t_fast = CanaryProber(fast, period=50).maybe_reprobe()
    assert t_slow > t_fast + 20.0
    # the dead cross-pod term is gone: design depends only on pod position
    assert np.array_equal(sim.design[:64], sim.design[64:])
