"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` first thing; it
never runs at package import.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing is changed.  Otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the path is
part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
