"""Operating-point generalization: N-axis (timing x voltage x temperature x
refresh) sweeps, the retention error channel, and the op-grid machinery.

The contracts under test (see ARCHITECTURE.md "operating points"):

  * the 4-timing-axis sweep through the generalized machinery is
    BIT-IDENTICAL to the pre-refactor path — anchored against the untouched
    legacy NumPy walker, and across dense / streamed / sharded legs;
  * axis grids live behind ``timing.AxisSpec`` and must survive the
    quantized hash-key round trip exactly (aliasing grids are rejected at
    construction);
  * the batched N-axis grid (``operating_grid_arrays``) reproduces the
    per-point NumPy reference (``DimmModel.operating_point_eval``)
    decision for decision;
  * per-bank tables stay inside the whole-DIMM envelope on every axis, in
    each axis's safe direction (<= on descending timing/vdd, >= on the
    ascending refresh axis);
  * the ``fail_prob`` kernel with both channel flags off is
    value-identical on a 15-wide operating-point row and on the 9-wide one
    (``tests/test_fail_prob_substrate.py``).
"""
import jax
import numpy as np
import pytest

from repro.core.geometry import SMALL, TINY
from repro.core.population import make_population
from repro.core.profiling import ALDRAM, DivaProfiler, diva_profile_loop
from repro.core.substrate import (GRIDS, TIMING_GRIDS, DimmBatch,
                                  lifetime_population, operating_grid_arrays,
                                  operating_points_population,
                                  profile_population_arrays)
from repro.core.streaming import (stream_operating_grid,
                                  stream_profile_population)
from repro.core.timing import (AXES, EXTENDED_AXES, PARAMS, STANDARD,
                               VDD_STD, AxisSpec, OperatingPoint,
                               TimingParams, op_point_key, timing_axis)
from repro.sharding import chunk_spans, dimm_mesh

POP = make_population(TINY, 8)
BATCH = DimmBatch.from_population(POP)
R = TINY.rows_per_mat
WORST_ROWS = np.array([0, R - 1])

POINTS = [
    OperatingPoint(),
    OperatingPoint(vdd=1.05),
    OperatingPoint(refresh_ms=256.0, temp_C=75.0),
    OperatingPoint(timing=TimingParams(10.0, 25.0, 10.0, 10.0), vdd=1.20),
]


def _meshes():
    meshes = [dimm_mesh(1)]
    if jax.device_count() > 1:
        meshes.append(dimm_mesh())
    return meshes


# ------------------------------------------------------ AxisSpec contracts

def test_axis_grids_deduped_behind_axisspec():
    """Satellite: substrate grids ARE the AxisSpec grids (no parallel copy)."""
    for p in PARAMS:
        assert TIMING_GRIDS[p] == AXES[p].grid
        assert GRIDS[p] == AXES[p].grid
    assert GRIDS["vdd"] == AXES["vdd"].grid
    assert GRIDS["refresh"] == AXES["refresh"].grid


def test_axis_grid_values_survive_quantization():
    """Every grid value and the standard round-trip the hash quantizer
    exactly — the draw key IS the quantized value, so aliasing would merge
    distinct sweep steps into one draw."""
    for name, spec in AXES.items():
        for v in spec.grid + (spec.standard,):
            q = spec.quantize(v)
            assert abs(q * spec.quant - v) < 1e-9, (name, v)
        keys = [spec.quantize(v) for v in spec.grid]
        assert len(set(keys)) == len(keys), name


def test_axisspec_rejects_aliasing_grid():
    with pytest.raises(ValueError, match="quantiz"):
        timing_axis("trp", step=2.4, floor=5.0)  # 11.35 not on the 0.25 grid
    with pytest.raises(ValueError, match="quantiz"):
        AxisSpec("vdd", "V", 4, 1.35, (1.30, 1.2501), quant=0.0125)


def test_axisspec_rejects_colliding_keys():
    with pytest.raises(ValueError, match="collide"):
        AxisSpec("x", "ns", 0, 10.0, (5.0, 5.0), quant=0.25)


def test_op_point_key_folds_all_coordinates():
    k0 = op_point_key(7, 104, 256)
    assert k0 == op_point_key(7, 104, 256)  # pure
    assert k0 != op_point_key(8, 104, 256)
    assert k0 != op_point_key(7, 105, 256)
    assert k0 != op_point_key(7, 104, 512)


# -------------------------------- 4-axis bit-parity (the banks=1 trick)

def test_four_axis_sweep_bit_identical_to_legacy_walker():
    """The generalized machinery at axes=PARAMS reduces to the pre-refactor
    program: same tables, bit for bit, as the untouched per-DIMM NumPy
    walker (the pre-refactor anchor)."""
    arr = profile_population_arrays(BATCH, axes=PARAMS)
    for i, d in enumerate(POP):
        t = diva_profile_loop(d, with_ecc=False)
        np.testing.assert_array_equal(
            arr[i], np.float32([getattr(t, p) for p in PARAMS]))


def test_four_axis_dense_streamed_sharded_identical():
    ref = profile_population_arrays(BATCH, axes=PARAMS)
    for chunk in (1, 3, 16):
        st = stream_profile_population(BATCH, chunk_size=chunk, collect=True)
        np.testing.assert_array_equal(ref, st["tables"], err_msg=f"{chunk=}")
    for mesh in _meshes():
        out = profile_population_arrays(BATCH, axes=PARAMS, mesh=mesh)
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


def test_extended_axes_keep_timing_prefix_bitwise():
    """One-knob-at-a-time: adding vdd/refresh axes (and the retention
    channel on them) cannot move the timing sweeps' draws or lambdas."""
    base = profile_population_arrays(BATCH)
    ext = profile_population_arrays(BATCH, axes=EXTENDED_AXES, retention=True)
    assert ext.shape == (len(POP), len(EXTENDED_AXES))
    np.testing.assert_array_equal(base, ext[:, : len(PARAMS)])


def test_extended_axes_columns_land_on_grid():
    ext = profile_population_arrays(BATCH, axes=EXTENDED_AXES, retention=True)
    for col, name in ((4, "vdd"), (5, "refresh")):
        allowed = set(np.float32(AXES[name].grid)) | {np.float32(
            AXES[name].standard)}
        assert set(ext[:, col].tolist()) <= {float(v) for v in allowed}, name


def test_extended_axes_streamed_and_sharded_identical():
    ref = profile_population_arrays(BATCH, axes=EXTENDED_AXES, retention=True)
    for chunk in (3, 16):
        st = stream_profile_population(BATCH, chunk_size=chunk,
                                       axes=EXTENDED_AXES, retention=True,
                                       collect=True)
        np.testing.assert_array_equal(ref, st["tables"], err_msg=f"{chunk=}")
    for mesh in _meshes():
        out = profile_population_arrays(BATCH, axes=EXTENDED_AXES,
                                        retention=True, mesh=mesh)
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


def test_operating_points_population():
    pts = operating_points_population(BATCH)
    assert len(pts) == len(POP)
    for pt in pts:
        assert isinstance(pt, OperatingPoint)
        assert pt.vdd <= VDD_STD + 1e-9
        assert pt.refresh_ms >= 64.0
        assert pt.energy_proxy() <= OperatingPoint().energy_proxy() + 1e-9


# ------------------------------------------- per-bank envelope property

def _envelope_ok(per_bank, whole, axes):
    for i, a in enumerate(axes):
        col_b, col_w = per_bank[:, :, i], whole[:, None, i]
        if AXES[a].descending:
            ok = (col_b <= col_w + 1e-6).all()
        else:
            ok = (col_b >= col_w - 1e-6).all()
        assert ok, (a, col_b, col_w)


def test_bank_tables_inside_whole_dimm_envelope_extended():
    whole = profile_population_arrays(BATCH, axes=EXTENDED_AXES,
                                      retention=True)
    per_bank = profile_population_arrays(BATCH, axes=EXTENDED_AXES,
                                         retention=True, banks=2)
    assert per_bank.shape == (len(POP), 2, len(EXTENDED_AXES))
    _envelope_ok(per_bank, whole, EXTENDED_AXES)


def test_bank_envelope_property_hypothesis():
    pytest.importorskip("hypothesis", reason="hypothesis not installed; "
                        "property sweep runs in CI")
    from hypothesis import given, settings, strategies as st

    pop = make_population(SMALL, 4)
    batch = DimmBatch.from_population(pop)

    @settings(max_examples=8, deadline=None)
    @given(temp=st.sampled_from([30.0, 55.0, 85.0]),
           refresh=st.sampled_from([64.0, 128.0]),
           banks=st.sampled_from([2, 4]),
           guard=st.integers(min_value=0, max_value=2))
    def prop(temp, refresh, banks, guard):
        kw = dict(axes=EXTENDED_AXES, retention=True, temp_C=temp,
                  refresh_ms=refresh, guard_cycles=guard)
        whole = profile_population_arrays(batch, **kw)
        per_bank = profile_population_arrays(batch, banks=banks, **kw)
        _envelope_ok(per_bank, whole, EXTENDED_AXES)

    prop()


# --------------------------------------------- N-axis operating grid

def test_operating_grid_matches_numpy_reference():
    res = operating_grid_arrays(BATCH, POINTS)
    assert res["fails"].shape == (len(POP), len(POINTS))
    for gi, pt in enumerate(POINTS):
        for di, d in enumerate(POP):
            f, lam = d.operating_point_eval(pt, WORST_ROWS)
            assert f == bool(res["fails"][di, gi]), (gi, di)
            np.testing.assert_allclose(lam, res["lam"][di, gi], rtol=2e-4,
                                       atol=1e-7)


def test_operating_grid_sharded_parity():
    ref = operating_grid_arrays(BATCH, POINTS)
    for mesh in _meshes():
        out = operating_grid_arrays(BATCH, POINTS, mesh=mesh)
        np.testing.assert_array_equal(ref["fails"], out["fails"])
        np.testing.assert_array_equal(ref["lam"], out["lam"])


def test_stream_operating_grid_matches_dense():
    dense = operating_grid_arrays(BATCH, POINTS)
    for chunk in (1, 3, 16):
        st = stream_operating_grid(BATCH, POINTS, chunk_size=chunk,
                                   collect=True)
        # decisions are bit-identical at any chunk size (serial-keyed draws);
        # lambdas are float32 reductions whose fusion varies with the chunk
        # program's width — tolerance-stable, the module's float contract
        np.testing.assert_array_equal(dense["fails"], st["fails"])
        np.testing.assert_allclose(dense["lam"], st["lam"], rtol=1e-5,
                                   atol=1e-7)
        np.testing.assert_array_equal(dense["fails"].sum(axis=0),
                                      st["fail_count"])
        np.testing.assert_allclose(dense["fails"].mean(axis=0),
                                   st["fail_stats"]["mean"])


def test_retention_lambda_monotone_in_refresh_interval():
    """Longer refresh interval => strictly more retention stress => the
    two-channel lambda is nondecreasing at fixed timing/vdd/temp."""
    pts = [OperatingPoint(refresh_ms=r) for r in (64.0, 128.0, 256.0, 512.0)]
    lam = operating_grid_arrays(BATCH, pts)["lam"]
    assert (np.diff(lam, axis=1) >= -1e-6).all()


def test_operating_grid_condition_rule():
    """Temperature is a condition, never a draw key: two points differing
    only in temp_C share their uniform draw, so a DIMM that fails at the
    cooler point cannot pass at the hotter one (lambda only grows)."""
    pts = [OperatingPoint(refresh_ms=256.0, temp_C=55.0),
           OperatingPoint(refresh_ms=256.0, temp_C=85.0)]
    res = operating_grid_arrays(BATCH, pts)
    assert (res["lam"][:, 1] >= res["lam"][:, 0] - 1e-6).all()
    assert (res["fails"][:, 1] | ~res["fails"][:, 0]).all()


# -------------------------------------------------- profiler-layer faces

def test_diva_profiler_operating_point():
    prof = DivaProfiler(POP[0], axes=EXTENDED_AXES, retention=True, banks=2)
    t = prof.timing()
    assert isinstance(t, TimingParams)
    assert prof.bank_table().shape == (2, len(PARAMS))
    assert prof.axis_table().shape == (2, len(EXTENDED_AXES))
    pt = prof.operating_point()
    assert isinstance(pt, OperatingPoint)
    assert pt.vdd <= VDD_STD + 1e-9 and pt.refresh_ms >= 64.0
    # whole-DIMM-safe: the envelope covers both banks in each direction
    tab = prof.axis_table()
    assert pt.vdd >= tab[:, 4].max() - 1e-6
    assert pt.refresh_ms <= tab[:, 5].min() + 1e-6


def test_aldram_axis_table():
    al = ALDRAM.install(POP[0], temps=(55.0, 85.0), axes=EXTENDED_AXES,
                        retention=True)
    assert al.axis_table(55.0).shape == (1, len(EXTENDED_AXES))
    assert al.bank_table(55.0).shape == (1, len(PARAMS))
    assert isinstance(al.timing(85.0), TimingParams)


def test_lifetime_extended_axes_shapes_and_prefix():
    ages = np.float32([0.0, 4.0])
    temps = np.float64([55.0, 55.0])
    base = lifetime_population(BATCH, ages, temps, diagnostics=False)
    ext = lifetime_population(BATCH, ages, temps, diagnostics=False,
                              axes=EXTENDED_AXES, retention=True)
    assert ext["timings"].shape == (2, len(POP), len(EXTENDED_AXES))
    np.testing.assert_array_equal(base["timings"],
                                  ext["timings"][:, :, : len(PARAMS)])


# ------------------------------------------------ chunk_spans edge cases

def test_chunk_spans_chunk_larger_than_population():
    assert chunk_spans(5, 100) == [(0, 5)]


def test_chunk_spans_chunk_one():
    assert chunk_spans(3, 1) == [(0, 1), (1, 2), (2, 3)]


def test_chunk_spans_exact_division_no_zero_width_tail():
    spans = chunk_spans(8, 4)
    assert spans == [(0, 4), (4, 8)]
    assert all(lo < hi for lo, hi in spans)
    assert chunk_spans(0, 4) == []


def test_chunk_spans_invalid_args():
    with pytest.raises(ValueError):
        chunk_spans(4, 0)
    with pytest.raises(ValueError):
        chunk_spans(-1, 4)
