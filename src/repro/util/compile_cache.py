"""One place for JAX's persistent compilation cache.

The drivers and ``chip_smoke.py`` call ``enable_compile_cache()`` before
their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it on its own and nothing is set here.  Otherwise the cache lives at a
fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory is part of the cache key, so a path that moved between runs
would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; returns it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
