"""Persistent XLA compile cache, placed from outside the program.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`enable_compile_cache` once before their first compile; library
modules and tests never do.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads that
variable itself), and otherwise in the fixed ``<repo>/.jax_cache``: the
directory is part of what a later run must find again, so it never moves
with a temp dir, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
