"""JAX's persistent compilation cache, kept at one fixed path.

Entry points call :func:`enable` once, before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout: the directory is part of each entry's key, so a path derived
from a temp name, a PID or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    # engine kernels compile in well under JAX's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CHECKOUT_CACHE)
