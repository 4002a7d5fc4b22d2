"""JAX's persistent compilation cache, kept in one fixed place.

Call :func:`enable_compile_cache` first thing in an entry point, before
anything compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets nothing. Otherwise the cache goes to
:data:`DEFAULT_DIR`, a fixed directory inside the checkout (listed in
``.gitignore``): the cache's path is part of how entries are found
again, so it never depends on a temporary name, a process id or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
