"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`use_compile_cache` before their first compile. Library code never
does: importing ``repro`` leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and git-ignored: the directory is part of the cache key, so a path
# derived from a temporary name, a pid or the time would never hit
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads the
    variable itself, and nothing else is set. Otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
