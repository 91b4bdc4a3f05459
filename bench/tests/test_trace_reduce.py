"""The trace reduction: interval arithmetic on a synthetic trace, and the
whole reduction on a small trace recorded on the CPU."""
from types import SimpleNamespace as NS

import numpy as np

import trace_reduce as tr


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=[])


def _profile():
    tpu = ', custom_call_target="tpu_custom_call"'
    ops = [_ev("%while.3 = (s32[]) while(s32[] %p)", 100, 300),
           _ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 150, 100),
           _ev("%custom-call.2 = f32[8] custom-call(), custom_call_target="
               '"AllocateBuffer"', 150, 10),
           _ev("%vmap_jit_fail_prob__.9 = f32[4] custom-call(f32[4] %x)"
               + tpu, 600, 100),
           _ev("%bank_sched.2 = (s32[8]) custom-call(s32[8] %q)" + tpu,
               750, 50)]
    mods = [_ev("jit__unknown(11)", 100, 300),
            _ev("jit__unknown(22)", 600, 100),
            _ev("jit__speedup_impl(33)", 750, 50)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev("bench.call", 0, 500), _ev("bench.call", 500, 500),
        _ev("PJRT_Client_Compile", 420, 150)])])
    return NS(planes=[dev, host])


def test_union_merges_nested_and_overlapping_intervals():
    s = np.asarray([0.0, 2.0, 1.0, 10.0])
    e = np.asarray([5.0, 3.0, 6.0, 12.0])
    total, merged = tr.union_seconds(s, e)
    assert total == 8.0
    assert merged.tolist() == [[0.0, 6.0], [10.0, 12.0]]


def test_kernel_names_come_from_custom_call_instructions():
    tpu = ', custom_call_target="tpu_custom_call"'
    assert tr.kernel_name("%vmap_jit_fail_prob__.9 = f32[4] custom-call("
                          "f32[4] %x)" + tpu) == "fail_prob"
    assert tr.kernel_name("%bank_sched.8 = (s32[8]) custom-call(s32[8] "
                          "%q)" + tpu) == "bank_sched"
    assert tr.kernel_name("%custom-call.3 = f32[8] custom-call(), "
                          'custom_call_target="AllocateBuffer"') is None
    assert tr.kernel_name("%fusion.1 = f32[8] fusion(f32[8] %a)") is None


def test_reduce_busy_idle_programs_and_gaps():
    red = tr.reduce(_profile())
    assert np.isclose(red["window_s"], 1000e-9)
    assert np.isclose(red["busy_s"], 450e-9)          # 300 + 100 + 50
    by_fp = {p["fingerprint"]: p for p in red["programs"]}
    assert by_fp["22"]["kernels"] == ["fail_prob"]
    assert by_fp["11"]["kernels"] == []
    assert np.isclose(by_fp["11"]["seconds"], 300e-9)
    assert np.isclose(red["kernels"]["bank_sched"], 50e-9)
    longest = red["gaps"][0]
    assert np.isclose(longest["seconds"], 200e-9)      # [400, 600)
    assert longest["label"] == "bench.call/PJRT_Client_Compile"
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "jit__unknown(11)"
    assert bd["device_ops"][1][0] == "jit__unknown(22)+fail_prob"


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    red = tr.reduce(tr.load(tr.find_trace(tmp_path)),
                    plane_prefix="/host:CPU", op_line="tf_XLA*")
    assert red["spans"] == 3
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["gaps"] and all(g["seconds"] > 0 for g in red["gaps"])
