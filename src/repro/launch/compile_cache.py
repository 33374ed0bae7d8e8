"""JAX's persistent compilation cache for the entry points.

Called at the start of ``chip_smoke.py`` and of the serving and training
drivers' ``main`` — never at import, so tests and library users keep
JAX's own defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and keeps the
cache there; nothing else is set. Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path (never one made from a temporary
name, a pid or the time), because a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
