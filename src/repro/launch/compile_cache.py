"""JAX's persistent compile cache for the entry points that run on a chip.

``python chip_smoke.py``, ``python -m repro.bench.run`` and
``python -m repro.launch.train`` call :func:`use_compile_cache` before their
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and the cache lives there; otherwise it goes to ``.jax_cache/`` at
the root of the checkout (git-ignored).  The path is part of every cache
key, so it is one fixed directory, never a temporary, per-process or dated
name.  Tests do not call this: they compile what they need in-process.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
