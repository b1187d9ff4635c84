"""JAX persistent compilation cache placement.

One rule for every entry point: when `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it itself and nothing is set in code; otherwise the cache lives
at the fixed `<checkout>/.jax_cache` (listed in .gitignore).  The path is
part of a cache entry's key, so it never depends on a temp name, a pid or
the time — a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the directory
    `JAX_COMPILATION_CACHE_DIR` names, or else `DEFAULT_DIR`.  Call first,
    before anything compiles.  -> the directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
