"""Placement of JAX's persistent compilation cache for the repo's entry points.

A cold process compiles every program it runs; the persistent cache lets a
later process in the same checkout reuse those executables. Entry points
(``chip_smoke.py``, ``benchmarks/run.py``) call :func:`configure_compile_cache`
from their ``main()`` — never at import time, so importing the library leaves
JAX's configuration untouched.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "configure_compile_cache"]

# <checkout>/.jax_cache (gitignored). A fixed path: the cache directory is
# part of what a later run must find again, so it is never derived from a
# temp name, a pid or the time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return that directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and this
    sets no other directory. Otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
