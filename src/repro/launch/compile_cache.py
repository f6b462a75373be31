"""JAX's persistent compilation cache, placed for the entry points.

A cold TPU run recompiles every bucket executable of the served path
(tens of seconds each). The persistent cache lets processes that share a
directory skip those compiles. Its key includes the directory's path, so
the path must not move between runs: it is either the one the operator
gives in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself)
or a fixed directory inside the checkout, never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (listed in ``.gitignore``).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache and nothing is set here; otherwise the cache goes to
    `REPO_CACHE_DIR`. Call it once, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
