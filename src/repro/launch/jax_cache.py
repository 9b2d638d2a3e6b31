"""Where JAX keeps its persistent compilation cache.

Only entry points call :func:`use_persistent_cache`, before their first
compile; importing a library module or a test never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/launch/jax_cache.py -> three levels up
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set. Otherwise the cache lives in ``.jax_cache/`` at the
    checkout root, a fixed path so that a later run finds what an earlier
    one compiled. Every compile is cached, since
    the served kernels each compile in well under JAX's default one-second
    threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
