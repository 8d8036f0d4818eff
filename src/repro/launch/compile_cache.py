"""JAX's persistent compilation cache, kept in one fixed place.

The cache key includes the cache directory, so a directory that moves between
runs never hits.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads
it itself); otherwise the cache lives at ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
