"""JAX's persistent compilation cache, at one fixed place.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module sets nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nowhere
else.  Otherwise it lives in ``.jax_cache/`` at the root of the
checkout, found from the package path: the directory is part of what a
later run must find again, so it is never a temporary one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: a serving run compiles many small ones
    # (sampling, block-table updates) besides the decode step
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
