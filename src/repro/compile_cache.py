"""JAX's persistent compilation cache, turned on by entry points only.

Every chunk program of the fleet path (ingest, campaign, conventional
sweep, scrub, memsim grid) compiles once per shape; at ``geometry.FULL`` on
a TPU that is tens of seconds per program, paid again by every cold
process.  Entry points (``chip_smoke.py``, ``launch/serve.py``, the bench
scripts) call :func:`enable_compile_cache` before their first compile.
Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout cache directory used when the environment names none
#: (listed in ``.gitignore``); fixed, so that every run of one checkout
#: finds the programs the previous run stored
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
