"""JAX's persistent compilation cache, placed from outside the program.

Call :func:`enable_compile_cache` before the first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set; otherwise the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, ignored by git). The path is part of each entry's
key, so it never depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
