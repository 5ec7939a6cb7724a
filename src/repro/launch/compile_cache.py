"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) call :func:`enable_compile_cache`
once at start-up; library modules never do, so importing them leaves
JAX's configuration untouched.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the directory is part of the cache key, so a cache that
# moved with each run would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the place: JAX reads it
    itself and this sets nothing.  Otherwise the cache lives in
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
