"""JAX's persistent compile cache at one fixed place inside the checkout.

``bench/run.py`` calls :func:`use_compile_cache` before its first compile;
importing the library sets no cache.  The directory is fixed (it is part of
the cache key, so a directory that moves never hits) and lies inside the
checkout, so that two checkouts share nothing.  Every program is cached,
however short its compile, so that a run after the first compiles nothing.
"""
from __future__ import annotations

from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at ``<checkout>/.jax_cache``; return it."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
