"""JAX's persistent compilation cache at one fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the entry points (``chip_smoke.py``,
`repro.launch.serving.main`, ``benchmarks/common``) keep the cache in
``<repo>/.jax_cache``: the path is part of the cache key, so it never
carries a temporary name, a pid or a time.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
