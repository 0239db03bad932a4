"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``launch/train.py``, ``benchmarks/run.py`` and the
examples call :func:`enable_compile_cache` at start-up; importing this module
does nothing, and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, so a later run of the same checkout hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself);
    otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
