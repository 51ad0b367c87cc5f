"""The chip smoke script and the compile-cache rule of the entry points.

``chip_smoke.py`` must refuse to run (non-zero exit, no verdict line) where
JAX finds no TPU, and its phases must keep running end to end: here they
run on the CPU at small sizes with the Pallas kernels interpreted, which
checks control flow and results, not the chip.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_smoke_phases_run_on_cpu(monkeypatch):
    smoke = _load_smoke()
    monkeypatch.setitem(smoke.KERNEL_FLAGS, "interpret", True)
    smoke.single_chip(n_whatif=40, n_clients=8, n_mc=256)


def test_compile_cache_leaves_env_setting_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
