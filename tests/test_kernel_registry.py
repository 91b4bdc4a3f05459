"""Backend authority, kernel registry, and tile autotuner contracts.

The dispatch layer's promises: one ``backend_tag()`` authority with a fixed
resolution order (force_backend > REPRO_FORCE_REF > REPRO_BACKEND > platform
default), derived ``use_pallas()``/``interpret_mode()`` views that are
correct on EVERY platform (the old heuristic special-cased TPU and silently
interpreted on GPU), a registry that stays in lockstep with the eight public
dispatch sites, and an autotuner that sweeps at most once per (kernel,
backend, bucket, mode) and never under a trace.
"""
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops, ref, tune
from repro.kernels.registry import GPU, KERNEL_NAMES, REGISTRY, TPU

RNG = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_REF", raising=False)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)


# ------------------------------------------------------------- backend_tag

def test_cpu_default_is_ref():
    """The perf flip this layer exists for: CPU defaults to the jnp oracle
    graphs, not interpret-mode Pallas."""
    assert ops.backend_tag() == "cpu-ref"
    assert ops.use_pallas() is False
    assert ops.interpret_mode() is True


def test_env_backend_resolves(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "cpu-pallas-interpret")
    assert ops.backend_tag() == "cpu-pallas-interpret"
    assert ops.use_pallas() is True
    assert ops.interpret_mode() is True


def test_force_ref_beats_env_backend(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "cpu-pallas-interpret")
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    assert ops.backend_tag() == "cpu-ref"


def test_invalid_env_backend_raises(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "tpu-mosaic")  # not valid on cpu
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        ops.backend_tag()
    monkeypatch.setenv("REPRO_BACKEND", "interpret-mode")  # legacy literal
    with pytest.raises(ValueError):
        ops.backend_tag()


def test_force_backend_nests_and_restores(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    with ops.force_backend("cpu-pallas-interpret"):
        # stronger than every env var, including FORCE_REF
        assert ops.backend_tag() == "cpu-pallas-interpret"
        with ops.force_backend("cpu-ref"):
            assert ops.backend_tag() == "cpu-ref"
        assert ops.backend_tag() == "cpu-pallas-interpret"
    assert ops.backend_tag() == "cpu-ref"


def test_force_backend_invalid_tag_raises():
    with pytest.raises(ValueError, match="invalid"):
        with ops.force_backend("gpu-triton"):  # wrong platform
            pass
    assert ops.backend_tag() == "cpu-ref"  # stack not corrupted


def test_gpu_host_would_compile_not_interpret(monkeypatch):
    """The bug this PR fixes: the old ``interpret_mode()`` special-cased TPU
    alone, so a GPU host silently ran every kernel interpreted.  With the
    platform stubbed to gpu, the default tag must be the compiled Triton
    route and ``interpret_mode()`` must be False."""
    monkeypatch.setattr(ops, "_platform", lambda: "gpu")
    assert ops.backend_tag() == GPU
    assert ops.use_pallas() is True
    assert ops.interpret_mode() is False
    assert set(ops.valid_tags()) == {"gpu-ref", "gpu-pallas-interpret", GPU}


def test_tpu_host_defaults_to_mosaic(monkeypatch):
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert ops.backend_tag() == TPU
    assert ops.interpret_mode() is False


# ---------------------------------------------------------------- registry

def test_registry_covers_the_eight_dispatch_sites():
    assert KERNEL_NAMES == (
        "secded_encode", "secded_syndrome", "fail_prob", "bit_signature",
        "bank_sched", "diva_shuffle", "rc_transient", "wkv6")
    for name in KERNEL_NAMES:
        assert callable(getattr(ops, name)), name


def test_registry_specs_well_formed():
    for name, spec in REGISTRY.items():
        assert spec.name == name
        assert spec.tile_space[0] == {}, \
            f"{name}: tile_space[0] must be the do-nothing default"
        assert callable(spec.pallas) and callable(spec.bucket)
        # oracle is LATE-BOUND on the ref module (monkeypatch visibility)
        assert spec.oracle is getattr(ref, name)
    assert REGISTRY["wkv6"].compiled == (TPU,), \
        "wkv6's VMEM scratch is TPU-only; GPU must fall back to the oracle"


def test_oracle_dispatch_is_late_bound(monkeypatch):
    calls = []
    orig = ref.secded_encode
    monkeypatch.setattr(ref, "secded_encode",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    data = RNG.integers(0, 2, (8, 64)).astype(np.int32)
    with ops.force_backend("cpu-ref"):
        ops.secded_encode(data)
    assert calls, "registry captured the oracle at import time — " \
                  "monkeypatching ref.<name> must reach dispatch"


def test_wkv6_compiled_route_falls_back_to_oracle_on_gpu(monkeypatch):
    """A kernel with no compiled lowering on this hardware routes to its
    oracle (counted as <plat>-ref), never silently interprets."""
    monkeypatch.setattr(ops, "_platform", lambda: "gpu")
    route, tag = ops._resolve(REGISTRY["wkv6"], None)
    assert (route, tag) == ("ref", "gpu-ref")
    route, tag = ops._resolve(REGISTRY["secded_encode"], None)
    assert (route, tag) == ("compiled", GPU)


def test_explicit_pallas_true_overrides_ref_tag():
    """pallas=True on a *-ref tag forces the interpret route — the
    test_memsim convention for exercising the kernel on CPU."""
    route, tag = ops._resolve(REGISTRY["secded_encode"], True)
    assert (route, tag) == ("interpret", "cpu-pallas-interpret")
    route, tag = ops._resolve(REGISTRY["secded_encode"], False)
    assert (route, tag) == ("ref", "cpu-ref")


# --------------------------------------------------------------- autotuner

def _sweeps(kernel: str, backend: str) -> int:
    return int(obs.REGISTRY.value("repro_kernel_tune_total",
                                  kernel=kernel, backend=backend))


def test_autotune_sweeps_once_per_bucket(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tune.clear()
    code = RNG.integers(0, 2, (100, 72)).astype(np.int32)
    before = _sweeps("secded_syndrome", "cpu-pallas-interpret")
    with ops.force_backend("cpu-pallas-interpret"):
        a = ops.secded_syndrome(code)
        b = ops.secded_syndrome(code)          # same bucket: cache hit
        c = ops.secded_syndrome(code[:97])     # 97 -> same pow2 bucket (128)
    assert _sweeps("secded_syndrome", "cpu-pallas-interpret") == before + 1
    win = tune.lookup("secded_syndrome", "cpu-pallas-interpret",
                      tune.bucket_pow2(100))
    assert win is not None and win in [dict(t) for t in
                                       REGISTRY["secded_syndrome"].tile_space]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c),
                                  np.asarray(ref.secded_syndrome(code[:97])))
    tune.clear()


def test_autotune_never_sweeps_under_a_trace(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tune.clear()
    code = RNG.integers(0, 2, (64, 72)).astype(np.int32)
    before = _sweeps("secded_syndrome", "cpu-pallas-interpret")
    with ops.force_backend("cpu-pallas-interpret"):
        out = jax.jit(lambda c: ops.secded_syndrome(c))(code)
    assert _sweeps("secded_syndrome", "cpu-pallas-interpret") == before, \
        "tracer args must resolve to defaults silently, never time a sweep"
    assert tune.lookup("secded_syndrome", "cpu-pallas-interpret",
                       tune.bucket_pow2(64)) is None
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.secded_syndrome(code)))
    tune.clear()


def test_autotune_disabled_on_interpret_without_optin():
    tune.clear()
    code = RNG.integers(0, 2, (32, 72)).astype(np.int32)
    before = _sweeps("secded_syndrome", "cpu-pallas-interpret")
    with ops.force_backend("cpu-pallas-interpret"):
        ops.secded_syndrome(code)
    assert _sweeps("secded_syndrome", "cpu-pallas-interpret") == before


def test_tune_cache_persistence_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tune.clear()
    code = RNG.integers(0, 2, (40, 72)).astype(np.int32)
    with ops.force_backend("cpu-pallas-interpret"):
        ops.secded_syndrome(code)
    bucket = tune.bucket_pow2(40)
    win = tune.lookup("secded_syndrome", "cpu-pallas-interpret", bucket)
    assert win is not None
    path = tune.save_cache(tmp_path / "TUNE_kernels.json")
    tune.clear()
    assert tune.lookup("secded_syndrome", "cpu-pallas-interpret",
                       bucket) is None
    assert tune.load_cache(path) >= 1
    assert tune.lookup("secded_syndrome", "cpu-pallas-interpret",
                       bucket) == win
    # loaded winners are plain JSON round-trippable dicts
    assert json.loads(path.read_text())
    tune.clear()


def test_autotune_winner_is_keyed_on_the_call_mode(tmp_path, monkeypatch):
    """A winner stored for a grid-mode ``fail_prob`` call is not handed to
    a row-sum call or a two-channel call of the same bucket: each mode
    sweeps for itself, and the persisted key carries the mode."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tune.clear()
    row_src = RNG.integers(0, 64, 64).astype(np.int32)
    d_mat = np.linspace(0.1, 1.0, 2).astype(np.float32)
    cf = np.array([3.9, 2.1, 0.4, 0.8, 0.4, 7.5, 0.15, 3e-6, 3.5,
                   1.2, 4.0, 0.4, 1.0, 0.3, 1.2], np.float32)
    tag, bucket = "cpu-pallas-interpret", tune.bucket_pow2(64)
    before = _sweeps("fail_prob", tag)
    with ops.force_backend(tag):
        ops.fail_prob(row_src, d_mat, cf[:9], cols=16)
        assert tune.lookup("fail_prob", tag, bucket) is not None
        for mode, kw in (("reduce_rows", dict(reduce_rows=True)),
                         ("voltage+retention",
                          dict(voltage=True, retention=True))):
            assert tune.lookup("fail_prob", tag, bucket, mode) is None
            ops.fail_prob(row_src, d_mat, cf, cols=16, **kw)
            assert tune.lookup("fail_prob", tag, bucket, mode) is not None
    assert _sweeps("fail_prob", tag) == before + 3
    modes = ("", "reduce_rows", "voltage+retention")
    assert sorted(tune._TUNE_CACHE) == [("fail_prob", tag, bucket, mode)
                                        for mode in modes]
    wins = {mode: tune.lookup("fail_prob", tag, bucket, mode)
            for mode in modes}
    path = tune.save_cache(tmp_path / "TUNE_kernels.json")
    assert sorted(json.loads(path.read_text())) == [
        f"fail_prob|{tag}|{bucket}|{mode}" for mode in modes]
    tune.clear()
    assert tune.load_cache(path) == 3
    assert {mode: tune.lookup("fail_prob", tag, bucket, mode)
            for mode in modes} == wins
    tune.clear()


def test_bucket_pow2():
    assert [tune.bucket_pow2(n) for n in (1, 2, 3, 100, 128, 129)] == \
        [1, 2, 4, 128, 128, 256]


def test_load_cache_missing_file_is_zero(tmp_path):
    assert tune.load_cache(tmp_path / "absent.json") == 0


# ------------------------------------------------- no hidden interpret route

def _tiny_call(name):
    """(args, kwargs minus ``interpret``) of a small call to ``name``."""
    i32 = lambda *shape: np.zeros(shape, np.int32)
    f32 = lambda *shape: np.zeros(shape, np.float32)
    if name == "fail_prob":
        return (i32(8), f32(2), f32(9)), dict(cols=8)
    if name == "bank_sched":
        q, b, r = i32(8), i32(4), i32(2)
        return ((q, q, q, q, q, b, b, b, r, r, r, i32(1), i32(4, 6), b, b),
                dict(tbl=4, trrd=5, tfaw=24, use_bus=True, use_act=True))
    return {"secded_encode": ((i32(8, 64),), {}),
            "secded_syndrome": ((i32(8, 72),), {}),
            "bit_signature": ((i32(8, 8),), dict(nbits=3)),
            "diva_shuffle": ((i32(8, 576),), {}),
            "rc_transient": ((f32(8), f32(8)), {}),
            "wkv6": ((f32(1, 8, 1, 4),) * 4 + (f32(1, 4),), {})}[name]


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_pallas_impl_requires_explicit_interpret(name):
    """Only the dispatcher chooses interpret-vs-compiled: an implementation
    called without ``interpret=`` is an error, never a silent interpreter
    run on an accelerator."""
    args, kw = _tiny_call(name)
    with pytest.raises(TypeError, match="interpret"):
        REGISTRY[name].pallas(*args, **kw)
