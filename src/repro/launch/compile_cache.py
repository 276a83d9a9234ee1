"""JAX's persistent compilation cache, set once by each entry point.

Compiling a full-width engine (init, one admission program per prompt
bucket, decode) dominates a cold start, so entry points keep compiled
executables on disk.  The directory is ``JAX_COMPILATION_CACHE_DIR`` where
that is set (JAX reads it itself), and otherwise ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a later run must find.
Nothing sets the cache on import; tests leave it off.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
