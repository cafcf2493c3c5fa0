"""JAX's persistent compilation cache at one fixed path per checkout."""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: ``<repo>/.jax_cache`` (git-ignored).  Fixed, because the path is
#: part of the cache key: a directory that moves never hits.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  Call it from script entry points only
    (``__main__`` blocks), never from library code or a ``main(argv)``
    that tests import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
