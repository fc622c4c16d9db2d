"""JAX's persistent compilation cache for the entry points.

Called by the scripts a user runs (``chip_smoke.py``, ``repro.launch.serve``),
never on library import: a library that picked a cache directory would
override the caller's.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, so every later run from the same
# checkout finds what an earlier one compiled
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to the fixed
    DEFAULT_DIR inside the checkout."""
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
