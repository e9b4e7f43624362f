"""Placement of JAX's persistent compilation cache for the entry points.

Entry points (``launch/serve.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` from ``main``; importing this module changes
nothing, and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored), so every later process of
    this checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
