"""Where the entry points keep JAX's persistent compilation cache.

The cache path is part of every entry's key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
it itself), otherwise ``<repo>/.jax_cache``. Entry points call
:func:`use_compile_cache` at the start of ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
