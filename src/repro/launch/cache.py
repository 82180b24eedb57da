"""Persistent compilation cache for the command-line entry points.

Called first thing by ``chip_smoke.py`` and the ``repro.launch`` mains,
never at import time, so tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what a later run must find again (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
