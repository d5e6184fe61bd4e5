"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``,
``examples/serve_lm.py``, the harness CLI) call :func:`enable_compile_cache`
once at start-up; importing ``repro`` never switches the cache on.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set in code.
* Otherwise the cache lives at the fixed ``<repo>/.jax_cache`` (gitignored).
  The directory is part of what a later run must find again, so it never
  comes from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_CACHE_DIR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
