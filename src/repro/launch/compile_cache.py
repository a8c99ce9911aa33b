"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call `enable_compile_cache()` before their first compile, so
their processes share compiled programs across runs. Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and this sets no
other directory. Otherwise the cache is ``<repo>/.jax_cache`` (git-ignored):
a fixed path, because the path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: repository root: src/repro/launch/compile_cache.py -> parents[3]
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
