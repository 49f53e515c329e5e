"""JAX's persistent compilation cache for the entry points.

Each entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) calls ``enable_compile_cache()`` first thing in its
``main``; importing this module changes nothing.  The cache key includes the
directory, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it
is set (JAX reads it itself), else ``.jax_cache`` at the root of the
checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
