"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.launch.serve``, ``benchmarks.run``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so every run from this checkout finds the entries of the last
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself), else ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
