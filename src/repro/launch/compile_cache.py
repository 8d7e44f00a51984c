"""JAX's persistent compilation cache, switched on by the entry points.

`enable_compile_cache` is called once by each entry point (``chip_smoke.py``,
``launch/train.py``, ``benchmarks/*``, ``examples/*``) before its first
compile; importing this module changes nothing, and the tests never call it.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
no other is set. Otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(listed in ``.gitignore``), so that every later run of the same checkout
finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
