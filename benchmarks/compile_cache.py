"""JAX's persistent compile cache at one fixed place, for the entry scripts.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`use_compile_cache`
before their first compile; importing the library sets no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set here; otherwise the cache is ``<repo>/.jax_cache``
    (a fixed path: the directory is part of the cache key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
