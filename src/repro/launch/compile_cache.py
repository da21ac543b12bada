"""Where entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so a path that moves between runs
never hits: the default is a fixed ``.jax_cache/`` inside the checkout.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Entry points call :func:`enable_compile_cache` once, before
their first compile; importing the library never touches the cache.
"""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Use ``root/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names a
    directory; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.fspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
