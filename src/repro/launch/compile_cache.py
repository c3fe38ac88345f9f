"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``examples/drl_cylinder.py``, the
``tools/launch_fleet.py`` runner role, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile, so a second run of
the same shapes loads its executables instead of compiling them again.

- Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no directory.
- Otherwise the cache lives at the fixed ``<checkout>/.jax_cache`` (listed
  in ``.gitignore``).  The path is part of each entry's key, so it is never
  a temporary directory, a pid or a time.

The test suite keeps the cache off (``JAX_ENABLE_COMPILATION_CACHE=false``
in ``tests/conftest.py``); the directory set here is then never written.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compile."""
    where = os.environ.get(CACHE_DIR_ENV)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
