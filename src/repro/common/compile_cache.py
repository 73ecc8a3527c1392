"""Where JAX keeps its persistent compilation cache.

Entry points (`repro.launch.train`, `chip_smoke.py`) call
`enable_compile_cache()` before their first compile. The cache key
includes the directory, so the directory is fixed: when
`JAX_COMPILATION_CACHE_DIR` is set JAX reads it and nothing here
overrides it; otherwise the cache lives at `<repo>/.jax_cache`, which
`.gitignore` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
