"""Persistent compilation cache placement for the entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and ``python -m
repro.bench`` call :func:`place_compile_cache` before their first compile.
Importing the library never does, so tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: Fixed directory inside the checkout (``src/repro/launch`` -> root).  The
#: path is part of the cache key, so it never comes from a pid, the time or a
#: temporary name: a directory that moves never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at :data:`CACHE_DIR`.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Returns the directory it set, or None.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
