"""JAX's persistent compilation cache, placed from outside.

Called by the entry points (``chip_smoke.py``, ``launch/train.py``,
``benchmarks/run.py``), never when the library is imported.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at a fixed path inside the checkout
(``.jax_cache``, gitignored): a directory that moves between runs would
never hit.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
