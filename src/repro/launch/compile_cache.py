"""Where JAX keeps its persistent compilation cache, for entry points.

DESIGN.md §6 (fused runner): the fused window scan compiles once per
config, and a cold compile is a large share of a short run.  Entry points
(``chip_smoke.py``, ``benchmarks/*.py``) call ``enable_compile_cache()``
once at start-up; the library never does it at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  overridden.
* Otherwise the cache lives at ``.jax_cache/`` in the checkout root — a
  fixed path, because the path is part of the cache key.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
