"""JAX's persistent compilation cache, at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there. Otherwise the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) put it at ``.jax_cache/`` in
the checkout: a path that does not move between runs, so a second run
finds what the first compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, kernels included, not only slow compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
