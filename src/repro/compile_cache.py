"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/train_hopgnn.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before they
compile anything; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here. Otherwise the cache lives in ``<checkout>/.jax_cache``: a fixed
path, so every run from this checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
