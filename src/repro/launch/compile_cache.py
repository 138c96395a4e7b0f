"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` first
thing; importing a module never touches the cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at one fixed, gitignored path in the
checkout (``<repo>/.jax_cache``): the path is part of the cache key, so a
directory that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
