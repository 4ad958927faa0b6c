"""JAX's persistent compilation cache, placed from outside.

Entry points (the edge-cloud launcher, the benchmark ``main``\\ s, the chip
smoke run) call :func:`enable_compile_cache` once before their first
compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and this sets
  no other path.
* Unset: the cache lives at ``<checkout>/.jax_cache`` — a fixed path, since
  the path is part of what a later run must find again.

The minimum compile time for an entry drops to zero: every executable of
the paper's forecaster compiles in well under JAX's default one-second
threshold, which would otherwise cache none of them.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
