"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` once, before their
first compile, so a later process reuses what an earlier one compiled
instead of recompiling every layer from cold. Library code and tests never
call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed. Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout — a fixed path, so every process of the checkout
    finds the same entries (``.gitignore`` lists it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
