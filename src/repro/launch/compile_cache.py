"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/engine_bench.py``) call :func:`enable_compile_cache` when they
start; importing this module sets nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return where.  A set
    ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left alone;
    otherwise the cache sits at the fixed ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
