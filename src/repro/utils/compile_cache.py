"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the fleet CLI, ``benchmarks.run``) call
:func:`use_compile_cache` once, before their first compile. Importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``, so every run from one checkout shares it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
