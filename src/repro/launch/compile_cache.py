"""JAX's persistent compilation cache, kept in one fixed place.

A compiled executable is found again only under the same cache directory,
so the directory never comes from a temporary name, a process id or the
time.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
cache stays there; otherwise it goes to ``.jax_cache/`` at the checkout
root (listed in ``.gitignore``).  Launchers call :func:`enable_compile_cache`
at the top of their ``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
