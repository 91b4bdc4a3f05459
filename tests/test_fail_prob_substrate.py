"""Parity tests: Pallas fail_prob kernel vs jnp oracle vs NumPy DimmModel,
and the batched population profiler vs the legacy per-DIMM walker."""
import numpy as np
import pytest

from repro.core.errors import DimmModel
from repro.core.geometry import SMALL, TINY
from repro.core.latency import vendor_models
from repro.core.population import make_population
from repro.core.substrate import (DimmBatch, fail_prob_grids,
                                  profile_population, query_uniform,
                                  row_error_lambda)
from repro.core.profiling import (conventional_profile_loop, diva_profile,
                                  diva_profile_loop)

POP = make_population(SMALL, 12)  # >= 8 DIMMs spanning all three vendors
BATCH = DimmBatch.from_population(POP)


# ------------------------------------------------------------------ hashing

def test_query_uniform_numpy_jax_bit_identical():
    import jax.numpy as jnp
    sub = np.arange(4)[:, None]
    pat = np.arange(4)[None, :]
    serial = np.full((4, 4), 7, np.uint32)
    u_np = query_uniform(serial, 2, 30, 1, sub, pat, xp=np)
    u_jx = np.asarray(query_uniform(jnp.asarray(serial), 2, 30, 1,
                                    jnp.asarray(sub), jnp.asarray(pat),
                                    xp=jnp))
    np.testing.assert_array_equal(u_np, u_jx)
    assert (u_np >= 0).all() and (u_np < 1).all()
    assert len(np.unique(u_np)) == 16  # distinct queries, distinct draws


# ------------------------------------------------------------ kernel parity

def _op_coeffs():
    cf9 = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                   np.float32)
    extra = np.array([1.2, 4.0, 0.4, 1.0, 0.3, 1.2], np.float32)
    return cf9, np.concatenate([cf9, extra])


@pytest.mark.parametrize("voltage,retention",
                         [(False, False), (True, False), (False, True),
                          (True, True)])
def test_fail_prob_kernel_matches_ref(voltage, retention):
    """Pallas (interpret) and the pure-jnp oracle share the formula helper;
    XLA fuses the two programs differently (FMA contraction), so agreement
    is to 1 float32 ulp, not literal bit equality.  With a channel flag on
    the row is 15 wide and the extra channels are live."""
    from repro.kernels import ref
    from repro.kernels.fail_prob import fail_prob as fp_pallas
    rng = np.random.default_rng(3 if not (voltage or retention) else 6)
    row_src = rng.integers(0, 64, 64).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 4).astype(np.float32)
    cf9, cf15 = _op_coeffs()
    coeffs = cf15 if voltage or retention else cf9
    flags = dict(voltage=voltage, retention=retention)
    k = np.asarray(fp_pallas(row_src, d_mat, coeffs, cols=64, interpret=True,
                             **flags))
    r = np.asarray(ref.fail_prob(row_src, d_mat, coeffs, cols=64, **flags))
    assert k.shape == (4, 64, 64)
    if voltage or retention:
        np.testing.assert_allclose(k, r, atol=1e-5, rtol=1e-5)
        # two summed per-cell channel probabilities: in [0, 2] on both paths
        assert (k >= 0).all() and (k <= 2).all()
    else:
        np.testing.assert_allclose(k, r, atol=1e-6, rtol=0)
        # probabilities stay in range on both paths
        assert (k >= 0).all() and (k <= 1).all()


def test_fail_prob_flags_off_15_wide_row_identical_to_9_wide():
    """With both channel flags off the kernel reads the row's first 9
    entries and traces the exact access-channel graph: a 15-wide row gives
    the 9-wide row's grid, bit for bit, on both routes."""
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    row_src = rng.integers(0, 64, 64).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 4).astype(np.float32)
    cf9, cf15 = _op_coeffs()
    for pallas in (True, False):
        np.testing.assert_array_equal(
            np.asarray(ops.fail_prob(row_src, d_mat, cf9, cols=64,
                                     pallas=pallas)),
            np.asarray(ops.fail_prob(row_src, d_mat, cf15, cols=64,
                                     pallas=pallas)))


@pytest.mark.parametrize("param,t_op,pattern,subarray,chip",
                         [("trp", 7.5, "0101", 0, 0),
                          ("trcd", 10.0, "0000", 2, 3),
                          ("tras", 22.5, "1001", 1, 0)])
def test_fail_prob_kernel_matches_numpy_grid(param, t_op, pattern, subarray,
                                             chip):
    """The kernel path reproduces DimmModel.fail_prob_grid per DIMM (both
    float32; folded coefficients cost a few ulp, bounded at 1e-5)."""
    g = np.asarray(fail_prob_grids(BATCH, param, t_op, refresh_ms=256.0,
                                   pattern=pattern, subarray=subarray,
                                   chip=chip))
    for i in (0, 5, 11):
        ref = POP[i].fail_prob_grid(param, t_op, refresh_ms=256.0,
                                    pattern=pattern, subarray=subarray,
                                    chip=chip)
        np.testing.assert_allclose(g[i], ref, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("voltage,retention",
                         [(False, False), (True, True)])
def test_fail_prob_dispatch_ref_mode(monkeypatch, voltage, retention):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    from repro.kernels import ops, ref
    row_src = np.arange(32, dtype=np.int32)
    d_mat = np.linspace(0.2, 1.0, 2).astype(np.float32)
    coeffs = np.array([4.0, 2.0, 0.5, 1.0, 0.3, 8.0, 0.2, 1e-5, 3.0],
                      np.float32)
    if voltage or retention:
        coeffs = _op_coeffs()[1]
    flags = dict(voltage=voltage, retention=retention)
    np.testing.assert_array_equal(
        np.asarray(ops.fail_prob(row_src, d_mat, coeffs, cols=32, **flags)),
        np.asarray(ref.fail_prob(row_src, d_mat, coeffs, cols=32, **flags)))


# --------------------------------------------------------- profiling parity

def test_profile_population_matches_legacy_loop_diva():
    """THE tentpole property: one jitted sweep == the per-DIMM NumPy walker,
    exactly, on >= 8 DIMMs (ECC criterion, 55C)."""
    batched = profile_population(BATCH, temp_C=55.0, multibit_only=True)
    assert len(batched) == 12
    for tp, dimm in zip(batched, POP):
        assert tp == diva_profile_loop(dimm, temp_C=55.0), dimm.serial


def test_profile_population_matches_legacy_loop_hot_no_ecc():
    batched = profile_population(BATCH, temp_C=85.0, multibit_only=False)
    for tp, dimm in zip(batched[:8], POP[:8]):
        assert tp == diva_profile_loop(dimm, temp_C=85.0, with_ecc=False)


def test_profile_population_matches_legacy_loop_conventional():
    sub = POP[:4]
    batched = profile_population(DimmBatch.from_population(sub), region="all",
                                 temp_C=55.0)
    for tp, dimm in zip(batched, sub):
        assert tp == conventional_profile_loop(dimm, temp_C=55.0)


def test_singleton_wrapper_consistent_with_batch():
    """diva_profile (the thin compat wrapper) == the population sweep entry."""
    batched = profile_population(BATCH, temp_C=55.0, multibit_only=True)
    for i in (0, 7, 11):
        assert diva_profile(POP[i], temp_C=55.0) == batched[i]


# ----------------------------------------------------------- count parity

def test_row_error_lambda_matches_numpy_expected_counts():
    lam = row_error_lambda(BATCH, "trp", 7.5, refresh_ms=256.0)
    for i in (0, 3, 9):
        ref = POP[i].row_error_counts("trp", 7.5, refresh_ms=256.0,
                                      sample=False)
        np.testing.assert_allclose(lam[i], ref, rtol=1e-4,
                                   atol=1e-5 * max(float(ref.max()), 1.0))


def test_row_error_lambda_internal_order_and_scramble():
    lam_int = row_error_lambda(BATCH, "trp", 7.5, refresh_ms=256.0,
                               internal_order=True)
    lam_ext = row_error_lambda(BATCH, "trp", 7.5, refresh_ms=256.0)
    R = SMALL.rows_per_mat
    for i in (0, 11):
        ext = np.asarray(POP[i].vendor.scramble.int_to_ext(np.arange(R)))
        for s in range(SMALL.subarrays):
            want = np.zeros(R, np.float32)
            want[ext] = lam_int[i, s * R:(s + 1) * R]
            np.testing.assert_allclose(lam_ext[i, s * R:(s + 1) * R], want,
                                       rtol=1e-6)


# -------------------------------------------------------------- RNG satellite

def test_count_queries_are_call_order_independent():
    """The shared-RNG nondeterminism fix: identical queries agree no matter
    what ran in between."""
    d1 = DimmModel(SMALL, vendor_models(SMALL)["A"], serial=0)
    a = d1.row_error_counts("trp", 7.5, refresh_ms=256.0)
    _ = d1.column_error_counts("trp", 7.5, refresh_ms=256.0)
    _ = d1.burst_bit_error_counts("trp", 7.5, refresh_ms=256.0)
    b = d1.row_error_counts("trp", 7.5, refresh_ms=256.0)
    np.testing.assert_array_equal(a, b)

    d2 = DimmModel(SMALL, vendor_models(SMALL)["A"], serial=0)
    np.testing.assert_array_equal(a, d2.row_error_counts("trp", 7.5,
                                                         refresh_ms=256.0))
    c1 = d1.column_error_counts("trp", 7.5, refresh_ms=256.0)
    c2 = d2.column_error_counts("trp", 7.5, refresh_ms=256.0)
    np.testing.assert_array_equal(c1, c2)
    b1 = d1.burst_bit_error_counts("trp", 7.5, refresh_ms=256.0)
    b2 = d2.burst_bit_error_counts("trp", 7.5, refresh_ms=256.0)
    np.testing.assert_array_equal(b1, b2)


def test_region_has_errors_deterministic_and_monotone_ish():
    d = DimmModel(TINY, vendor_models(TINY)["A"], serial=1)
    rows = np.arange(TINY.rows_per_mat)
    r1 = d.region_has_errors("trp", 5.0, rows, refresh_ms=256.0)
    r2 = d.region_has_errors("trp", 5.0, rows, refresh_ms=256.0)
    assert r1 == r2 == True  # near-total failure at 5 ns (Fig 6d)
    assert not d.region_has_errors("trp", 12.5, rows)  # margin region
