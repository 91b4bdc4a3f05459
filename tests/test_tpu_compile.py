"""Compile the fleet path's kernels for a TPU v5e at FULL geometry widths.

Interpret mode cannot see what Mosaic refuses (blocks not aligned to the
(8, 128) tiling, rank-1 blocks that are neither the whole axis nor a
multiple of 128 lanes, VMEM overruns).  The TPU compiler is installed with
JAX and compiles for a chip that is described rather than attached, so
every entry of every main-path kernel's registry tile space is compiled
here, at ``geometry.FULL`` widths, with no chip.  The tile autotuner sweeps
these spaces on the chip and no longer skips a refused tile, so a tile
that does not compile fails here first.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.geometry import FULL
from repro.core.latency import DEFAULT_ITERS
from repro.kernels.fail_prob import N_COEFFS, N_OP_COEFFS
from repro.kernels.registry import REGISTRY
from repro.memsim.sim import MemSimConfig

GiB = 2 ** 30
#: one campaign chunk of the chip smoke (chip_smoke.py ingests FULL fleets
#: in chunks of 64 DIMMs)
CHUNK = 64
#: SECDED / shuffle rows: one scrub chunk of served-table words
N_WORDS = 4096
MAIN_PATH = ("fail_prob", "bit_signature", "secded_encode",
             "secded_syndrome", "bank_sched", "diva_shuffle")
CASES = [(name, tiles) for name in MAIN_PATH
         for tiles in REGISTRY[name].tile_space]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _full_width_args(name: str, sharding):
    """(abstract args, static kwargs) of one kernel call at FULL widths."""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    g = FULL
    if name == "fail_prob":
        return (a((g.rows_per_mat,), jnp.int32), a((g.mats_x,), jnp.float32),
                a((N_COEFFS,), jnp.float32)), dict(cols=g.cols_per_mat)
    if name == "bit_signature":
        nbits = int(np.log2(g.rows_per_mat))
        return (a((CHUNK * g.subarrays, g.rows_per_mat), jnp.int32),), \
            dict(nbits=nbits)
    if name == "secded_encode":
        return (a((N_WORDS, 64), jnp.int32),), {}
    if name == "secded_syndrome":
        return (a((N_WORDS, 72), jnp.int32),), {}
    if name == "diva_shuffle":
        return (a((N_WORDS, 576), jnp.int32),), {}
    assert name == "bank_sched"
    cfg = MemSimConfig()
    q, b = (a((cfg.queue,), jnp.int32), a((cfg.banks,), jnp.int32))
    return ((q, q, q, q, q, b, b, b, a((cfg.channels,), jnp.int32),
             a((cfg.ranks,), jnp.int32), a((cfg.ranks,), jnp.int32),
             a((1,), jnp.int32), a((cfg.banks, 6), jnp.int32), b, b),
            dict(tbl=cfg.tbl, trrd=cfg.trrd, tfaw=cfg.tfaw, use_bus=cfg.bus,
                 use_act=cfg.act_window))


@pytest.mark.parametrize(
    "name,tiles", CASES,
    ids=[f"{n}-{'-'.join(f'{k}{v}' for k, v in t.items()) or 'default'}"
         for n, t in CASES])
def test_kernel_tile_compiles_for_v5e_at_full_width(one_chip, name, tiles):
    args, kw = _full_width_args(name, one_chip)
    fn = functools.partial(REGISTRY[name].pallas, interpret=False, **tiles,
                           **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "tiles", REGISTRY["fail_prob"].tile_space,
    ids=[f"row_tile{t['row_tile']}" if t else "default"
         for t in REGISTRY["fail_prob"].tile_space])
def test_fail_prob_two_channel_grid_compiles_for_v5e_at_full_width(one_chip,
                                                                   tiles):
    """The grid mode of ``fail_prob`` with the supply shift and the
    retention channel on, reading the 15-wide operating-point row from
    SMEM, at every tile of its registry space and FULL widths."""
    args, kw = _full_width_args("fail_prob", one_chip)
    args = args[:2] + (jax.ShapeDtypeStruct((N_OP_COEFFS,), jnp.float32,
                                            sharding=one_chip),)
    fn = functools.partial(REGISTRY["fail_prob"].pallas, interpret=False,
                           voltage=True, retention=True, **tiles, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_campaign_chunk_program_compiles_within_half_of_hbm(one_chip,
                                                            monkeypatch):
    """The ingest campaign (``_row_lambda_impl``: the ``fail_prob`` kernel
    vmapped over a chunk of DIMMs, summed per row) compiled for v5e at a
    FULL chunk of 64 DIMMs: the kernel lowers through Mosaic, and the
    program's largest intermediate — one (D, mats, rows, cols) f32 grid,
    16 MiB per DIMM — stays well under half of the chip's 16 GB."""
    from repro.core import substrate
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import pad_batch
    from repro.kernels import ops
    # the dispatcher asks the platform; steer it to the compiled route
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    batch = pad_batch(synthetic_fleet(2, FULL, seed=0).chunk(0, 2),
                      CHUNK - 2)

    def a(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    prog = functools.partial(substrate._row_lambda_impl, pidx=2,
                             iters=DEFAULT_ITERS, internal=True, pallas=True)
    compiled = jax.jit(prog).lower(
        jax.tree.map(a, batch),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((CHUNK,), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * GiB, mem


@pytest.mark.parametrize(
    "tiles", REGISTRY["fail_prob"].tile_space,
    ids=[f"row_tile{t['row_tile']}" if t else "default"
         for t in REGISTRY["fail_prob"].tile_space])
def test_fail_prob_row_sums_compile_for_v5e_at_full_width(one_chip, tiles):
    """The row-sum mode of ``fail_prob`` at every tile of its registry
    space: its loops, the row-block slices of the row source and the
    per-block stores compile through Mosaic at FULL widths."""
    args, kw = _full_width_args("fail_prob", one_chip)
    fn = functools.partial(REGISTRY["fail_prob"].pallas, interpret=False,
                           reduce_rows=True, **tiles, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _trace_reduce():
    """``bench/trace_reduce.py``, which names the kernels of a device trace."""
    path = Path(__file__).resolve().parents[1] / "bench" / "trace_reduce.py"
    spec = importlib.util.spec_from_file_location("trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_row_lambda_chunk_program_writes_no_cell_grid(one_chip, monkeypatch):
    """``_row_lambda_impl`` on a FULL chunk of 64 DIMMs sums the cells in
    the kernel: its temporaries stay under 64 MiB, where one (64, 16, 512,
    512) f32 grid alone is 1 GiB, and its one Pallas call reads as kernel
    ``fail_prob`` by the rule the benchmark's trace reduction names kernels
    by, so the campaign program is still found as the one that runs it."""
    from repro.core import substrate
    from repro.core.population import synthetic_fleet
    from repro.core.streaming import pad_batch
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    batch = pad_batch(synthetic_fleet(2, FULL, seed=0).chunk(0, 2),
                      CHUNK - 2)

    def a(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    prog = functools.partial(substrate._row_lambda_impl, pidx=2,
                             iters=DEFAULT_ITERS, internal=False, pallas=True)
    compiled = jax.jit(prog).lower(
        jax.tree.map(a, batch),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((CHUNK,), jnp.float32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem
    kernel_name = _trace_reduce().kernel_name
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert {kernel_name(c) for c in calls} == {"fail_prob"}


def test_memsim_grid_program_compiles_for_v5e(one_chip, monkeypatch):
    """The memsim speedup grid vmaps ``bank_sched`` over (tables x
    workloads): the batching rule prepends squeezed block dims, which
    Mosaic judges with the kernel's trailing block dims — a case the
    unbatched kernel compiles above cannot show."""
    from repro.kernels import ops
    from repro.memsim import sim
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    cfg = MemSimConfig()
    traces = sim._stack_traces(64, cfg.banks, 0)

    def a(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    prog = functools.partial(sim._speedup_impl, cfg=cfg, pallas=True)
    compiled = jax.jit(prog).lower(
        jax.tree.map(a, traces),
        jax.ShapeDtypeStruct((4, cfg.banks, 6), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((cfg.banks, 6), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
