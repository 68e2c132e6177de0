"""JAX's persistent compilation cache at a fixed path.

Entry points call ``enable_compile_cache()`` from ``main()`` — never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
cache there and nothing is changed; otherwise the cache goes to
``<checkout>/.jax_cache``, an absolute path fixed by the checkout (the
path is part of the cache key, so a directory that moves never hits).
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
