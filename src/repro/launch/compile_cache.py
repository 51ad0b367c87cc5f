"""Persistent XLA compilation cache for the repo's entry points.

``use_compile_cache()`` is called by the programs a user runs
(``chip_smoke.py``, ``perfbench/run.py``, ``python -m
repro.launch.analyze``), never at library import: tests and library users
keep JAX's default.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* unset: the cache goes to ``.jax_cache/`` at the checkout root.  The path
  is fixed (no temp name, pid or time) because it is part of the cache key:
  a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Switch on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
