"""JAX's persistent compilation cache, placed before the first compile.

Every JAX-backed entry point calls ``use_compile_cache()`` first, so
processes that compile the same programs share them. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache sits at one fixed path inside the checkout,
``<repo>/.jax_cache`` (gitignored): the directory is part of the cache key,
so it never depends on a temp name, a PID or the time.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point the persistent cache at its directory; return the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
