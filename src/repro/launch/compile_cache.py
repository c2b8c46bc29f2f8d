"""Persistent XLA compilation cache shared by the repo's entry points.

``chip_smoke.py``, ``examples/serve_e2e.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` first thing; library modules never touch the
cache, so importing them sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory: a cache that moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set.  Otherwise the cache is ``.jax_cache/``
    at the repository root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
