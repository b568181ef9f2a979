"""Where JAX keeps its persistent compilation cache.

A chip run compiles its programs cold unless an earlier run left them in
the persistent cache, and the cache's path is part of what it matches
on, so it must not move between runs. :func:`enable_compile_cache` is
called by the launchers' ``main``s and by ``chip_smoke.py``, never on
import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
