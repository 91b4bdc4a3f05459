"""Sharded-vs-single-device parity: every substrate entry point under a 1xN
DIMM-axis mesh (sharding.dimm_mesh + the shard_map shim) must be bit-identical
to the unsharded path — the counter-hash RNG is keyed by each DIMM's global
serial, which travels with its shard, so device placement cannot change draws.

A single-device mesh runs the same shard_map program and is tested
unconditionally; true multi-device parity (including the padding path for
D % n_devices != 0) runs when the runtime exposes > 1 device — CI forces this
with XLA_FLAGS=--xla_force_host_platform_device_count=2."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import shuffling
from repro.core.geometry import SMALL
from repro.core.population import make_population
from repro.core.substrate import (GRIDS, DimmBatch, fail_prob_grids,
                                  lifetime_population,
                                  profile_population_arrays, row_error_lambda,
                                  shuffling_gain_population)
from repro.core.timing import PARAMS
from repro.obs import REGISTRY
from repro.sharding import dimm_mesh

POP = make_population(SMALL, 6)
BATCH = DimmBatch.from_population(POP)

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="single-device runtime (use XLA_FLAGS="
           "--xla_force_host_platform_device_count=N)")


def _meshes():
    """Single-device mesh always; the full device mesh when it is bigger."""
    meshes = [dimm_mesh(1)]
    if jax.device_count() > 1:
        meshes.append(dimm_mesh())
    return meshes


# ------------------------------------------------------------ profiling

def test_profile_population_sharded_parity():
    ref = profile_population_arrays(BATCH, temp_C=55.0, multibit_only=True)
    for mesh in _meshes():
        out = profile_population_arrays(BATCH, temp_C=55.0,
                                        multibit_only=True, mesh=mesh)
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


@multidevice
def test_profile_population_sharded_parity_with_padding():
    """D not divisible by the mesh: the runner pads by cloning the last DIMM
    and slices back — kept DIMMs' draws are untouched (serial-keyed hash)."""
    n = jax.device_count()
    sub = DimmBatch.from_population(POP[:n + 1])
    ref = profile_population_arrays(sub, temp_C=85.0)
    out = profile_population_arrays(sub, temp_C=85.0, mesh=dimm_mesh())
    np.testing.assert_array_equal(ref, out)


def _grid_steps():
    """Grid points counted per parameter for the entry ``profile``."""
    return {p: sum(REGISTRY.value("repro_sweep_grid_steps_total",
                                  entry="profile", param=p, outcome=o)
                   for o in ("evaluated", "skipped"))
            for p in PARAMS}


@pytest.mark.parametrize("age", [0.0, 20.0, 80.0])
def test_profile_early_exit_sharded_parity(age):
    """Under shard_map each shard runs its own early-exit loop over its own
    DIMMs: the tables are the unsharded ones, and each call counts one
    grid per parameter, evaluated or skipped."""
    aged = dataclasses.replace(
        BATCH, age_years=np.full(BATCH.n_dimms, age, np.float32))
    kw = dict(temp_C=55.0, multibit_only=True, banks=2)
    ref = profile_population_arrays(aged, **kw)
    for mesh in _meshes():
        before = _grid_steps()
        out = profile_population_arrays(aged, mesh=mesh, **kw)
        after = _grid_steps()
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))
        for p, n in after.items():
            assert n - before[p] == len(GRIDS[p]), (p, mesh)


@multidevice
def test_profile_early_exit_sharded_parity_with_padding():
    """Shards whose loops end at different points: one DIMM that never
    fails keeps its shard's loops running to the floor, the others stop."""
    n = jax.device_count()
    sub = DimmBatch.from_population(POP[:n + 1])
    base = np.array(sub.base)
    base[0] = -50.0
    sub = dataclasses.replace(sub, base=base)
    ref = profile_population_arrays(sub, temp_C=55.0)
    out = profile_population_arrays(sub, temp_C=55.0, mesh=dimm_mesh())
    np.testing.assert_array_equal(ref, out)


# ------------------------------------------------------------ shuffling

def test_shuffling_gain_population_sharded_parity():
    probs = shuffling.design_stripe_profiles(6, seed=3)
    ref = shuffling_gain_population(probs, n_accesses=200)
    for mesh in _meshes():
        out = shuffling_gain_population(probs, n_accesses=200, mesh=mesh)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k],
                                          err_msg=f"{k} on {mesh}")


@multidevice
def test_shuffling_gain_population_sharded_parity_with_padding():
    n = jax.device_count()
    probs = shuffling.design_stripe_profiles(n + 1, seed=5)
    ref = shuffling_gain_population(probs, n_accesses=150)
    out = shuffling_gain_population(probs, n_accesses=150, mesh=dimm_mesh())
    for k in ref:
        np.testing.assert_array_equal(ref[k], out[k], err_msg=k)


# ------------------------------------------------------- grids and lambdas

def test_fail_prob_grids_sharded_parity():
    ref = np.asarray(fail_prob_grids(BATCH, "trp", 7.5, refresh_ms=256.0))
    for mesh in _meshes():
        out = np.asarray(fail_prob_grids(BATCH, "trp", 7.5, refresh_ms=256.0,
                                         mesh=mesh))
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


def test_row_error_lambda_sharded_parity():
    ref = row_error_lambda(BATCH, "trp", 7.5, refresh_ms=256.0)
    for mesh in _meshes():
        out = row_error_lambda(BATCH, "trp", 7.5, refresh_ms=256.0, mesh=mesh)
        np.testing.assert_array_equal(ref, out, err_msg=str(mesh))


# ---------------------------------------------------------- lifetime sweep

def test_lifetime_population_sharded_parity():
    ages = np.array([0.0, 4.0, 8.0], np.float32)
    temps = np.full(3, 55.0)
    ref = lifetime_population(BATCH, ages, temps)
    for mesh in _meshes():
        out = lifetime_population(BATCH, ages, temps, mesh=mesh)
        for k in ("timings", "stale_fail", "ecc_lambda"):
            np.testing.assert_array_equal(ref[k], out[k],
                                          err_msg=f"{k} on {mesh}")


@multidevice
def test_lifetime_population_sharded_parity_with_padding():
    n = jax.device_count()
    sub = DimmBatch.from_population(POP[:n + 1])
    ages = np.array([0.0, 6.0], np.float32)
    ref = lifetime_population(sub, ages, np.full(2, 70.0))
    out = lifetime_population(sub, ages, np.full(2, 70.0), mesh=dimm_mesh())
    for k in ("timings", "stale_fail", "ecc_lambda"):
        np.testing.assert_array_equal(ref[k], out[k], err_msg=k)
