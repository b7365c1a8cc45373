"""Persistent XLA compilation cache for the entry points.

``chip_smoke.py``, ``launch/train.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` once at start-up; importing the package never
does.  The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is
set, and otherwise in ``.jax_cache/`` at the root of the checkout.  The
path is part of the cache key, so it is fixed: never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
