"""Where compiled programs persist between runs of this checkout."""

from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache lives at ``<checkout>/.jax_cache`` —
    a fixed path, because the path is part of what a cached entry is
    found by.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
