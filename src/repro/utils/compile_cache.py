"""JAX's persistent compilation cache for the entry points.

Each entry point calls :func:`enable_compile_cache` first, so a process
reuses what an earlier process on the same machine compiled. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at a fixed ``<repo>/.jax_cache``
(the path takes part in the cache key, so a moving directory never
hits).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
