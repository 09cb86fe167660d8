"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, the examples and the benchmarks).

Call :func:`enable_compile_cache` from a ``__main__`` block, never at
import or from tests.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it and this sets nothing; otherwise the cache goes to a fixed
``<repo>/.jax_compile_cache``.  The directory is part of each entry's
key, so it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

#: fixed cache directory used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Returns the directory the cache lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
