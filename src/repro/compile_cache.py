"""JAX's persistent compilation cache, for entry points.

Call :func:`enable_compile_cache` at the start of a ``main``, before the
first compile; never at import, so a library user keeps their own setting.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

# <repo>/.jax_cache: a fixed path inside the checkout (the path is part of
# the cache's key, so a directory that moves never hits)
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE
