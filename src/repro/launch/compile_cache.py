"""Where the entry points keep JAX's persistent compilation cache.

Called from the entry points only (``launch.serve``, ``launch.train``,
``chip_smoke.py``), never at import: a library import must not change
process-wide JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# Fixed and inside the checkout (git-ignored): the directory is part of
# the cache key, so a path built from a temp name, pid or time never hits.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``.jax_cache`` at
    the repository root."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
