"""CPU-side guards of the chip entry points: the compile-cache helper and
``chip_smoke.py``'s refusal to run (or report success) without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert calls == [], "with the variable set, JAX reads it; set nothing"


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == REPO / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]


def test_chip_smoke_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_FORCE_REF", None)
    env.pop("REPRO_BACKEND", None)
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_a_backend_override():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_FORCE_REF="1")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "REPRO_FORCE_REF" in proc.stderr
