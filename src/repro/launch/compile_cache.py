"""One place that decides where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing this module changes nothing. When the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and this function
leaves it alone. Otherwise the cache goes to ``<repo>/.jax_cache`` — a
fixed, git-ignored path, because the directory is part of the cache key
and a path that moves between runs never hits.
"""
from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
