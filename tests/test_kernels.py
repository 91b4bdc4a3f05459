"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.latency import fail_probability
from repro.kernels import ops, ref
from repro.kernels import fail_prob as fp_mod
from repro.kernels.fail_prob import fail_prob as fp_pallas
from repro.kernels.rc_transient import rc_transient as rc_pallas
from repro.kernels.registry import REGISTRY
from repro.kernels.secded import encode_checks, syndrome
from repro.kernels.shuffle import apply_shuffle
from repro.kernels.wkv6 import wkv6 as wkv6_pallas

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n", [1, 7, 512, 1000, 2049])
def test_secded_encode_shapes(n):
    data = RNG.integers(0, 2, (n, 64)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(encode_checks(data, interpret=True)),
                                  np.asarray(ref.secded_encode(data)))


@pytest.mark.parametrize("n", [3, 256, 777])
def test_secded_syndrome_shapes(n):
    code = RNG.integers(0, 2, (n, 72)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(syndrome(code, interpret=True)),
                                  np.asarray(ref.secded_syndrome(code)))


@pytest.mark.parametrize("n", [1, 65, 300])
@pytest.mark.parametrize("inverse", [False, True])
def test_shuffle_kernel(n, inverse):
    b = RNG.integers(0, 2, (n, 576)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(apply_shuffle(b, inverse=inverse,
                                                        interpret=True)),
                                  np.asarray(ref.diva_shuffle(b, inverse)))


def test_shuffle_roundtrip():
    b = RNG.integers(0, 2, (50, 576)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(apply_shuffle(apply_shuffle(b, interpret=True),
                                 inverse=True, interpret=True)), b)


@pytest.mark.parametrize("n", [4, 130])
def test_rc_transient_kernel_vs_spice(n):
    rf = np.linspace(0.02, 0.98, n)
    cf = np.linspace(0.0, 1.0, n)
    kr = rc_pallas(rf, cf, interpret=True)
    rr = ref.rc_transient(rf, cf)
    np.testing.assert_allclose(np.asarray(kr["sense_t"]), rr["sense_t"], atol=0.02)
    np.testing.assert_allclose(np.asarray(kr["v_cell"]), rr["v_cell"], atol=2e-3)
    np.testing.assert_allclose(np.asarray(kr["v_probe"]), rr["v_probe"], atol=2e-3)


def test_rc_transient_monotone_in_distance():
    rf = np.linspace(0.05, 0.95, 8)
    out = np.asarray(rc_pallas(rf, np.zeros(8), interpret=True)["sense_t"])
    assert np.all(np.diff(out) >= -1e-6)


@pytest.mark.parametrize("B,S,H,dh", [(1, 64, 1, 8), (2, 96, 2, 16),
                                      (3, 130, 4, 32), (2, 64, 2, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_wkv6_kernel_sweep(B, S, H, dh, dtype):
    r, k, v, w = (RNG.normal(0, 0.5, (B, S, H, dh)).astype(dtype) for _ in range(4))
    u = RNG.normal(0, 0.1, (H, dh)).astype(np.float32)
    yk = np.asarray(wkv6_pallas(r, k, v, w, u, interpret=True), np.float32)
    yr = np.asarray(ref.wkv6(r, k, v, w, u), np.float32)
    tol = 2e-3 if dtype == np.float16 else 3e-4
    np.testing.assert_allclose(yk, yr, rtol=tol, atol=tol)


def test_ops_dispatch_ref_mode(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    data = RNG.integers(0, 2, (16, 64)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ops.secded_encode(data)),
                                  np.asarray(ref.secded_encode(data)))


# -------------------------------------------------- tiled-dispatch contracts
#
# The masked-tail + tile-invariance template every tiled kernel follows:
# pad-to-tile + slice-back must be invisible at ANY tile, including tiles
# that do not divide the axis.  Integer kernels assert EXACT equality;
# float kernels get tolerances — across *different* tiles XLA may fuse the
# single-block and multi-block grid programs differently (FMA contraction),
# which is ulp-scale jitter, not a semantic difference (ARCHITECTURE 3i).

@pytest.mark.parametrize("n,tile", [(1000, 128), (1000, 7), (5, 8),
                                    (2049, 512)])
def test_secded_masked_tail_non_dividing_tiles(n, tile):
    """The satellite template: SECDED parity at tiles that do NOT divide the
    codeword count (and a tile larger than the input)."""
    data = RNG.integers(0, 2, (n, 64)).astype(np.int32)
    code = RNG.integers(0, 2, (n, 72)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.secded_encode(data, tile=tile, pallas=True)),
        np.asarray(ref.secded_encode(data)))
    np.testing.assert_array_equal(
        np.asarray(ops.secded_syndrome(code, tile=tile, pallas=True)),
        np.asarray(ref.secded_syndrome(code)))


@pytest.mark.parametrize("tile", [None, 64, 100, 7])
def test_shuffle_and_signature_tile_invariant_exact(tile):
    b = RNG.integers(0, 2, (300, 576)).astype(np.int32)
    counts = RNG.integers(0, 2 ** 16, (150, 512)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.diva_shuffle(b, tile=tile, pallas=True)),
        np.asarray(ref.diva_shuffle(b)))
    np.testing.assert_array_equal(
        np.asarray(ops.bit_signature(counts, nbits=9, tile=tile,
                                     pallas=True)),
        np.asarray(ref.bit_signature(counts, 9)))


@pytest.mark.parametrize("q_tile", [None, 128, 256])
def test_bank_sched_tile_invariant_exact(q_tile):
    """Queue tiling pads with q_valid=0 slots (arbitration key 0, sliced
    off); per-candidate scoring is independent, so all-int outputs are
    exact at any q_tile, dividing or not (300 slots: three 128-blocks with
    a ragged tail, or one padded 256-block and a ragged second)."""
    rng = np.random.default_rng(11)
    q = 300
    args = (rng.integers(0, 16, q).astype(np.int32),
            rng.integers(0, 50, q).astype(np.int32),
            rng.integers(0, 2, q).astype(np.int32),
            rng.integers(0, 400, q).astype(np.int32),
            (rng.random(q) < 0.8).astype(np.int32),
            rng.integers(-1, 50, 16).astype(np.int32),
            rng.integers(0, 500, 16).astype(np.int32),
            rng.integers(-100, 500, 16).astype(np.int32),
            rng.integers(0, 500, 2).astype(np.int32),
            rng.integers(-100, 400, 2).astype(np.int32),
            rng.integers(-100, 400, 2).astype(np.int32),
            np.int32(120),
            rng.integers(4, 30, (16, 6)).astype(np.int32),
            (np.arange(16) % 2).astype(np.int32),
            (np.arange(16) % 2).astype(np.int32))
    kw = dict(tbl=4, trrd=5, tfaw=24, use_bus=True, use_act=True)
    want = [np.asarray(o) for o in ref.bank_sched(*args, **kw)]
    got = ops.bank_sched(*args, q_tile=q_tile, pallas=True, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("row_tile", [64, 96, 100])
def test_fail_prob_row_tiles_match_oracle_to_float_tolerance(row_tile):
    """Row tiling (masked tail included: 96/100 do not divide 512) vs the
    oracle.  NOT bitwise across tiles — multi-block grids fuse differently
    from the single-block program, amplified by erf-tail cancellation at
    tiny p — but bounded well inside the model's meaningful precision."""
    rng = np.random.default_rng(5)
    row_src = rng.integers(0, 512, 512).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 4).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                      np.float32)
    want = np.asarray(ref.fail_prob(row_src, d_mat, coeffs, cols=128))
    got = np.asarray(ops.fail_prob(row_src, d_mat, coeffs, cols=128,
                                   row_tile=row_tile, pallas=True))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-3)
    op_coeffs = np.concatenate(
        [coeffs, np.array([1.2, 4.0, 0.4, 1.0, 0.3, 1.2], np.float32)])
    want_op = np.asarray(ref.fail_prob(row_src, d_mat, op_coeffs, cols=128,
                                       voltage=True, retention=True))
    got_op = np.asarray(ops.fail_prob(row_src, d_mat, op_coeffs, cols=128,
                                      voltage=True, retention=True,
                                      row_tile=row_tile, pallas=True))
    np.testing.assert_allclose(got_op, want_op, atol=1e-5, rtol=1e-3)


def test_fail_prob_default_tile_bitwise_matches_untiled():
    """row_tile=None must keep the EXACT pre-registry graph (single-block
    grid) — the existing 1-f32-ulp oracle contracts ride on this."""
    rng = np.random.default_rng(6)
    row_src = rng.integers(0, 128, 128).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 3).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                      np.float32)
    from repro.kernels.fail_prob import fail_prob as fp_pallas
    np.testing.assert_array_equal(
        np.asarray(ops.fail_prob(row_src, d_mat, coeffs, cols=64,
                                 pallas=True)),
        np.asarray(fp_pallas(row_src, d_mat, coeffs, cols=64,
                             interpret=True)))


# (rows, mats, cols) of the row-sum cases: TINY's and FULL's widths, and
# 200 rows, which no tile of the registry divides
ROW_SUM_SHAPES = {"tiny": (64, 4, 64), "rows200": (200, 4, 128),
                  "full": (512, 16, 512)}
ROW_SUM_TILES = REGISTRY["fail_prob"].tile_space + ({"row_tile": 100},)


@pytest.mark.parametrize("shape", ROW_SUM_SHAPES)
@pytest.mark.parametrize(
    "tiles", ROW_SUM_TILES,
    ids=[str(t.get("row_tile", "default")) for t in ROW_SUM_TILES])
def test_fail_prob_row_sums_match_oracle(shape, tiles):
    """The row-sum mode (interpret) against the oracle's row sums, compiled
    as every caller compiles it, at every tile of the registry and one that
    divides no row count.  The kernel sums in another order than XLA, and
    the compiled formula rounds a few cells differently from the oracle's
    op-by-op evaluation, so the sums agree to 1e-5, not bit for bit."""
    R, M, C = ROW_SUM_SHAPES[shape]
    rng = np.random.default_rng(7)
    row_src = rng.integers(0, R, R).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, M).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                      np.float32)
    oracle = jax.jit(ref.fail_prob, static_argnames=("cols", "open_bitline",
                                                     "reduce_rows"))
    for open_bitline in (True, False):
        want = np.asarray(oracle(row_src, d_mat, coeffs, cols=C,
                                 open_bitline=open_bitline, reduce_rows=True))
        got = np.asarray(fp_pallas(row_src, d_mat, coeffs, cols=C,
                                   open_bitline=open_bitline, interpret=True,
                                   reduce_rows=True, **tiles))
        assert got.shape == (R,)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=0)


@pytest.mark.parametrize(
    "tiles", ROW_SUM_TILES,
    ids=[str(t.get("row_tile", "default")) for t in ROW_SUM_TILES])
def test_fail_prob_row_sums_with_both_channels_match_oracle(tiles):
    """The row-sum mode with the supply shift and the retention channel on
    (15-wide row) against the oracle's row sums, to the same 1e-5 as the
    access channel alone: the flags reach the same ``cell_probs`` in both
    output modes."""
    R, M, C = ROW_SUM_SHAPES["rows200"]
    rng = np.random.default_rng(9)
    row_src = rng.integers(0, R, R).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, M).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5,
                       1.2, 4.0, 0.4, 1.0, 0.3, 1.2], np.float32)
    flags = dict(voltage=True, retention=True)
    oracle = jax.jit(ref.fail_prob, static_argnames=(
        "cols", "open_bitline", "voltage", "retention", "reduce_rows"))
    want = np.asarray(oracle(row_src, d_mat, coeffs, cols=C,
                             reduce_rows=True, **flags))
    got = np.asarray(fp_pallas(row_src, d_mat, coeffs, cols=C,
                               interpret=True, reduce_rows=True, **tiles,
                               **flags))
    assert got.shape == (R,)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=0)


def test_fail_prob_oracle_row_sums_are_the_grid_summed():
    """The oracle's row-sum mode is its grid summed over mats and columns,
    bit for bit: the cpu-ref route traces the reduction it always did."""
    rng = np.random.default_rng(8)
    row_src = rng.integers(0, 128, 128).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 4).astype(np.float32)
    coeffs = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5],
                      np.float32)
    grid = ref.fail_prob(row_src, d_mat, coeffs, cols=64)
    np.testing.assert_array_equal(
        np.asarray(ref.fail_prob(row_src, d_mat, coeffs, cols=64,
                                 reduce_rows=True)),
        np.asarray(grid.sum(axis=(0, 2))))


@pytest.mark.parametrize("tile", [32, 100])
def test_rc_transient_tile_variants_within_tolerance(tile):
    rf = np.linspace(0.02, 0.98, 130)
    cf = np.linspace(0.0, 1.0, 130)
    base = ops.rc_transient(rf, cf, pallas=True)
    tiled = ops.rc_transient(rf, cf, tile=tile, pallas=True)
    for k in ("sense_t", "v_cell", "v_probe"):
        np.testing.assert_allclose(np.asarray(tiled[k]), np.asarray(base[k]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tile_bh,chunk", [(4, None), (None, 128), (3, 50)])
def test_wkv6_tile_variants_within_tolerance(tile_bh, chunk):
    r, k, v, w = (RNG.normal(0, 0.5, (2, 96, 2, 16)).astype(np.float32)
                  for _ in range(4))
    u = RNG.normal(0, 0.1, (2, 16)).astype(np.float32)
    base = np.asarray(ops.wkv6(r, k, v, w, u, pallas=True), np.float32)
    tiled = np.asarray(ops.wkv6(r, k, v, w, u, tile_bh=tile_bh, chunk=chunk,
                                pallas=True), np.float32)
    np.testing.assert_allclose(tiled, base, rtol=3e-4, atol=3e-4)


# Row-sum cases of (rows, mats, cols) = (200, 16, 1024), one repaired row.
# Strips step 2 ns apart (k_wl 16), so tiles lie at many z.  At t_op 7.5 ns
# every mode of ``saturation_modes`` engages; at 11.5 ns no term reaches 1,
# the sums stay small, and terms near the bound would show if a tile past
# it were not exactly 0.
SATURATION_CASE = """
import numpy as np
row_src = np.arange(200, dtype=np.int32)
row_src[150] = 101
d_mat = np.linspace(0.0, 1.0, 16).astype(np.float32)
cols = 1024
coeff_rows = {t_op: np.array([-5.75, 0.1, 16.0, 0.3, 0.6, t_op, 0.15, 3e-6,
                              2.0], np.float32) for t_op in (7.5, 11.5)}
"""
# sha256 of the row sums of the kernel that evaluated every term (its last
# form before the skip), per (t_op, bitline mode), the same at every tile
# of ROW_SUM_TILES, evaluated op by op (see below)
ROW_SUM_DIGESTS = {
    (7.5, True):
        "3f11888b72ed0cfe44abc62d4b0e00385b10f9eaccc4e333ccb30d6948081dfa",
    (7.5, False):
        "b86ecbd4e786caabd813b9e9481f2e14c3d7b1a02b3975f59077a5b703e1269c",
    (11.5, True):
        "52f96aca6429aac6c8704481ad8807c8dd00d94671f511553ba221401a2b19f4",
    (11.5, False):
        "1b812882edcc8433347409e6277cc85d467bad4e0d33c06f0541241edc59884b",
}


def _saturation_case():
    ns = {}
    exec(SATURATION_CASE, ns)
    return ns["row_src"], ns["d_mat"], ns["coeff_rows"], ns["cols"]


def test_fail_prob_row_sums_keep_the_digest_of_the_unskipped_kernel():
    """Skipping the saturated terms changes no bit of the row sums, in both
    bitline modes and at every tile of ROW_SUM_TILES.

    The host's compiler contracts a multiply and the add that consumes it
    into one fused multiply-add wherever they share a fusion, and the
    skipping kernel's branches cut fusions where the unskipped kernel had
    none, so on an FMA host the two round a few sums differently.  The
    digests were taken, and are checked, in a process whose compiler is
    held below FMA (``--xla_cpu_max_isa=AVX``): every operation rounds on
    its own, as the formula writes it."""
    code = SATURATION_CASE + f"""
import hashlib, json
from repro.kernels.fail_prob import fail_prob
tiles = {ROW_SUM_TILES!r}
print(json.dumps({{f"{{t_op}}|{{ob}}": [hashlib.sha256(np.asarray(fail_prob(
    row_src, d_mat, cf, cols=cols, open_bitline=ob, interpret=True,
    reduce_rows=True, **t)).tobytes()).hexdigest() for t in tiles]
    for t_op, cf in coeff_rows.items() for ob in (True, False)}}))
"""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=AVX", PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    digests = json.loads(out.stdout.strip().splitlines()[-1])
    for (t_op, ob), want in ROW_SUM_DIGESTS.items():
        assert digests[f"{t_op}|{ob}"] == [want] * len(ROW_SUM_TILES), \
            (t_op, ob)


@pytest.mark.parametrize("open_bitline", [True, False])
def test_fail_prob_mode_table_counts_every_tile_and_engages_every_mode(
        open_bitline):
    """The count beside the kernel sums the mode table it reads, one mode
    per (row block, column strip) tile for all its mats: evaluated plus
    saturated terms are twice the tiles times the mats, and the case the
    digest pins at 7.5 ns engages all five modes."""
    row_src, d_mat, coeff_rows, cols = _saturation_case()
    coeffs = coeff_rows[7.5]
    for tiles in ROW_SUM_TILES:
        row_tile = tiles.get("row_tile")
        layout = fp_mod._row_sum_layout(row_src, d_mat, coeffs, cols,
                                        open_bitline, False, row_tile)
        modes = np.asarray(layout[-1]).ravel()
        n_tiles = layout[0].shape[0] // 64 * (cols // 128)
        assert modes.size == n_tiles
        assert set(np.unique(modes)) == set(range(len(fp_mod.MODE_EVALS)))
        evaluated, saturated = np.asarray(fp_mod.erf_evals(
            row_src, d_mat, coeffs, cols=cols, open_bitline=open_bitline,
            row_tile=row_tile))
        assert evaluated + saturated == 2 * n_tiles * d_mat.size
        assert evaluated == d_mat.size * sum(fp_mod.MODE_EVALS[m]
                                             for m in modes)
        assert 0 < evaluated < 2 * n_tiles * d_mat.size


def test_fail_prob_erf_evals_count_all_terms_on_the_oracle_route():
    """The oracle skips nothing, so its route counts every term evaluated;
    the kernel's route counts from the mode table."""
    row_src, d_mat, coeff_rows, cols = _saturation_case()
    coeffs = coeff_rows[7.5]
    batch = (row_src[None], d_mat, coeffs[None])
    kernel = np.asarray(ops.fail_prob_erf_evals(*batch, cols=cols,
                                                pallas=True))
    oracle = np.asarray(ops.fail_prob_erf_evals(*batch, cols=cols,
                                                pallas=False))
    np.testing.assert_array_equal(kernel[0], fp_mod.erf_evals(
        row_src, d_mat, coeffs, cols=cols))
    np.testing.assert_array_equal(oracle[0], [kernel[0].sum(), 0])


def _saturation_z():
    """z on a dense grid past both thresholds, as t - t_op at sigma 1."""
    z = np.linspace(-40.0, 40.0, 1_600_001, dtype=np.float32)
    return z[np.abs(z) >= fp_mod.Z_SATURATED]


def _assert_saturated(z, p):
    p = np.asarray(p)
    assert np.all(p[z <= -fp_mod.Z_SATURATED] == 0.0)
    assert np.all(p[z >= fp_mod.Z_SATURATED] == 1.0)


def test_fail_probability_is_exactly_0_or_1_past_the_saturation_z():
    """The rule the kernel's skip rests on: past |z| = Z_SATURATED the
    failure probability is exactly 0.0 or 1.0 in float32, at any t_op and
    sigma that give the same z."""
    z = _saturation_z()
    for t_op, sigma in ((0.0, 1.0), (7.5, 0.15), (5.0, 0.2)):
        t = jnp.asarray(z * np.float32(sigma) + np.float32(t_op))
        zz = np.asarray((t - t_op) / sigma)
        _assert_saturated(zz, jax.jit(
            lambda t: fail_probability(t, t_op, sigma, xp=jnp))(t))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="checks the chip's own float32 arithmetic")
def test_fail_probability_saturates_inside_a_pallas_kernel_on_the_chip():
    """The same rule inside a Pallas kernel compiled for the chip, whose
    exp, divide and reciprocal are the chip's own."""
    from jax.experimental import pallas as pl
    z = _saturation_z()
    z = np.pad(z, (0, (-z.size) % 1024)).reshape(-1, 128)

    def kernel(z_ref, out_ref):
        out_ref[...] = fail_probability(z_ref[...], 0.0, 1.0, xp=jnp)

    p = pl.pallas_call(kernel, grid=(z.shape[0] // 8,),
                       in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                       out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                       out_shape=jax.ShapeDtypeStruct(z.shape, jnp.float32),
                       )(jnp.asarray(z))
    _assert_saturated(z, p)
