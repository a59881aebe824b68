"""JAX's persistent compilation cache, placed by the entry points.

A full-width model compiles each prefill bucket and decode width for tens
of seconds; the persistent cache lets a second process (or a second
engine in the same process) load those executables instead.  The cache
key includes its directory, so the directory is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself, and nothing here sets another), else ``.jax_cache`` at
the root of this checkout.  Call :func:`setup_compile_cache` from an
entry point's ``main`` — never at import — so library users keep their
own setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
