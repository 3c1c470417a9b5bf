"""JAX's persistent compilation cache, at a place that can be set from outside.

A compiled executable is keyed on, among other things, the cache's own
path, so a directory that moves between runs never hits.  Hence one
rule for every entry point (``chip_smoke.py``, ``benchmarks/run.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other;
* otherwise the fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/compile_cache.py``.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
