"""Where the entry points keep JAX's persistent compilation cache.

A process that finds a program in the cache skips its compilation, so
a second solve, benchmark or smoke run starts in seconds. The cache
only hits when the directory stays put: its path is part of what the
entries are found by, so it is never built from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is (JAX
    reads the variable itself) and nothing else is configured. Otherwise
    the cache lives in ``.jax_cache/`` at the root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
