"""Where JAX keeps its persistent compilation cache for this checkout.

Every program entry point calls :func:`enable_compile_cache` before its first
compile.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the
cache lives at the fixed ``<checkout>/.jax_cache`` (gitignored).  The path is
part of the cache's key, so it is never derived from a temp dir, a pid or the
time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that path.  Call before the first compile."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
