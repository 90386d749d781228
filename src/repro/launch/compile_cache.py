"""JAX's persistent compilation cache, kept where a later run finds it.

Entry points call :func:`enable_compile_cache` from their ``main()``;
importing this module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and this helper sets nothing. Otherwise the
cache goes to the fixed directory ``<repo>/.jax_cache``: the directory is
part of what a cached entry is found by, so it never depends on a temp
name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)

