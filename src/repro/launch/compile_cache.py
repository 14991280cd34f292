"""Persistent compile cache for the entry points.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``repro.launch.train``, ``repro.launch.serve_policy``, ``benchmarks.run``);
importing a module never does, and tests never do.  Compiles that take
longer than JAX's ``jax_persistent_cache_min_compile_time_secs`` (one
second by default) are then kept across processes.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Where the cache goes when the environment does not place it.  The path
#: is fixed inside the checkout so that a later run looks in the same place.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
