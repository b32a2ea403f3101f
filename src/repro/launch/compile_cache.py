"""JAX's persistent compilation cache for the drivers.

A run finds the programs an earlier run compiled only if it looks in
the same directory, so the cache lives at a path that is the same on
every run: never a temporary, pid- or time-derived directory.  Called
by the ``serve`` and ``train`` mains and by ``chip_smoke.py``; never at
import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (listed in .gitignore); this file lives at
# <checkout>/src/repro/launch/compile_cache.py
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    from the environment itself, so nothing else is set.  Otherwise the
    cache is :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
