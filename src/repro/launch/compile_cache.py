"""Persistent compilation cache placement for the entry points.

A cold chip run can spend much of its time compiling, and a cache helps only
where its directory is found again: the path is part of the cache's key.
"""

from __future__ import annotations

import os

import jax

#: Checkout root: this file lives at <root>/src/repro/launch/.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
    other directory is set here.  Otherwise the cache goes to the fixed
    ``.jax_cache/`` at the checkout root, so every run of this checkout
    finds the same entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
