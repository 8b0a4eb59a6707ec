"""Where JAX keeps its persistent compilation cache — decided in one place.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile, so processes that
compile the same programs share them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps
    the cache, and no other directory is set here.  Otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache``: never a temporary,
    per-process or timestamped name, so a later process finds what an
    earlier one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
