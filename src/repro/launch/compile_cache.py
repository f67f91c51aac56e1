"""Persistent XLA compilation cache, placed from outside the program.

``enable_compilation_cache`` is called by the entry points
(``chip_smoke.py``, ``repro.launch.train``), never while a module is
imported. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
that directory and nothing here overrides it. Otherwise the cache goes
to ``.jax_cache/`` at the root of the checkout. The path is part of
what a later run must find again, so it is fixed: never a temporary
name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
