"""Persistent XLA compile cache for the repository's entry points.

Scripts, examples and the benchmark harness call :func:`enable_compile_cache`
before their first compile; importing the library never touches the cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed
path, because the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (``src/repro/`` → checkout root)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
