"""Launchers: production mesh, multi-pod dry-run, train/serve drivers."""
from __future__ import annotations

import os
from pathlib import Path

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed directory at the root of the checkout (gitignored). The
# path is part of what a later run must find again, so it never varies.
COMPILE_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call it before the
    process compiles anything. With JAX_COMPILATION_CACHE_DIR set, JAX
    reads that directory itself and this sets nothing; otherwise the cache
    goes to `COMPILE_CACHE_DIR`. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
