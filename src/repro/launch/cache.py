"""Persistent XLA compilation cache for the entry-point scripts.

Entry points (``chip_smoke.py``, ``examples/scenario_sweep.py``) call
``enable_compile_cache()`` once at start-up. The library never sets a
cache on import, and neither do the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed path: the cache key includes it, so a directory that moves never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    this sets no other directory; otherwise the cache goes to
    ``.jax_cache/`` at the repo root. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
