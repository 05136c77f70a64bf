"""JAX's persistent compilation cache, placed from outside or at one
fixed directory inside the checkout.

Entry points that compile for the chip call :func:`enable_compile_cache`
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it on import and this module sets no other directory.
Otherwise the cache lives in :data:`DEFAULT_DIR`: a fixed path, never
one built from a temporary name, a pid or the time, so a later run in
the same checkout finds what an earlier one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
