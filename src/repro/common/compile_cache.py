"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` before their first compile.  A
``JAX_COMPILATION_CACHE_DIR`` in the environment is JAX's own setting
and is left alone; otherwise the cache lives at a fixed path inside the
checkout (``.jax_cache``, gitignored).  The path is part of the cache
key, so it must not move between runs.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point the persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
