"""JAX's persistent compilation cache, switched on by every entry point
before its first compile.

Compiling the train step or the serving engine at published widths takes
minutes on a cold start; with the cache on, a second run of the same
programs loads them instead.  The cache key includes the directory, so it
must be a fixed path: never a temp, pid or time-based one.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/cache.py);
# listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call before the first ``jit`` compiles."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
