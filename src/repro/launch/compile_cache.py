"""JAX's persistent compile cache, placed from outside.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, that
directory holds the cache and nothing here overrides it.  Otherwise the
cache lives in ``.jax_cache`` at the root of the checkout, a fixed path
resolved from this package's location, so a later run from the same
checkout finds the entries again.  Entry points call
:func:`enable_compile_cache` before their first compile; the test suite
turns the cache off in ``tests/conftest.py``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the checkout's."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
