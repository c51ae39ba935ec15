"""Where JAX keeps its persistent compilation cache.

A cold chip run compiles the corpus prefill, each prefill bucket and the
decode step; the cache lets a later process on the same machine load them
instead. The cache key includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that variable
itself), otherwise ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. Call before the first compilation of the process; later calls do
    not move a cache that is already in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
