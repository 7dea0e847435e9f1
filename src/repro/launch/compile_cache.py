"""The persistent JAX compilation cache of the entry points.

``python -m repro.launch.serve`` and ``chip_smoke.py`` call
``configure_compile_cache()`` once, before their first compile, so a
second run of either finds the programs the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache lives in ``.jax_cache/`` at
    the checkout root: a fixed path, because a cache whose directory
    moves between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
