"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

The cache is keyed partly by its directory, so the directory must not
move between runs: it is either ``$JAX_COMPILATION_CACHE_DIR`` (which JAX
reads on its own) or ``<checkout>/.jax_cache``, found from this file.
Entry points call ``enable_compile_cache()`` before their first compile;
tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed; otherwise the cache goes to the checkout's
    ``.jax_cache`` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)
