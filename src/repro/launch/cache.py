"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os

import jax

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden; otherwise the cache lives at
    ``<checkout>/.jax_cache``, a fixed path, so later runs from the same
    checkout find it again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
