"""Persistent XLA compilation cache shared by the entry points.

``enable_compile_cache()`` is the first thing ``chip_smoke.py``,
``repro.launch.serve`` and ``repro.launch.train`` call. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is set here. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git ignores it): the path is part of what a later
process looks up, so it never carries a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
