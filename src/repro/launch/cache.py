"""Persistent XLA compilation cache for the entry points.

JAX keys its persistent cache on the directory, so the cache lives at one
fixed place: where `JAX_COMPILATION_CACHE_DIR` says when the environment
sets it (JAX reads that variable itself, and nothing here overrides it),
else `<checkout>/.jax_cache`, which git ignores.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
