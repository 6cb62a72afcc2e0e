"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, before they compile
anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives in
``.jax_cache`` at the root of the checkout (listed in ``.gitignore``):
a fixed path, because the path is part of what a later run must find
again, so a temporary or per-process directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
