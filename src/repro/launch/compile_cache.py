"""Where entry points keep JAX's persistent compilation cache.

Call ``use_compile_cache()`` first thing in an entry point's ``main()``
(never at import).  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and this does nothing.  Otherwise the cache goes to ``.jax_cache/``
at the repository root: a fixed path, because the directory is part of the
cache key, so a path built from a temporary name, a process id or the time
would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
