"""The one place that turns on JAX's persistent compilation cache.

Every entry point that compiles (``chip_smoke.py``, ``benchmark/run.py``,
the test session, the replica and gateway workers) calls :func:`enable`
before its first compile, so processes that build the same traces share one
cache and only the first of them pays XLA.

The directory is part of what a hit depends on, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads the variable itself and no
directory is set in code; otherwise the cache is ``.jax_compile_cache`` at
the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache")


def enable() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable: with a time threshold, a compile that lands on
    # either side of it from one run to the next makes a warm run write
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
