"""JAX's persistent compilation cache, placed where a later run finds it.

``enable_compile_cache()`` is called by the entry points (``chip_smoke.py``,
``repro.launch.train``, ``repro.launch.serve``) before their first
compilation, never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is changed here; otherwise the cache goes to
``<checkout>/.jax_cache``, a fixed path, since the path is part of what a
later process must match to hit the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
