"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/fct_run.py``, ``launch/fct_serve.py``,
``benchmarks/run.py``) call :func:`setup_compile_cache` before their first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
and nothing here overrides it.  Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` — never a temporary, per-process or time-stamped path,
because the directory is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this module lives in (src/repro/launch/ -> root)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def setup_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
