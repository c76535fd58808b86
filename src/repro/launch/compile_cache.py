"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`enable_compile_cache` from their ``main()``;
nothing turns the cache on at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this sets no other directory; otherwise
the cache lives at a fixed ``<checkout>/.jax_cache`` — a fixed path,
because the path is part of the cache key.  Every program is cached,
not only those that took over a second to compile: a solve compiles
dozens of small programs, and together they are most of a run's
compile time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
