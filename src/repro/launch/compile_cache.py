"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move from
run to run.  An operator places it with ``JAX_COMPILATION_CACHE_DIR``, which
JAX reads itself; otherwise it lives at a fixed ``.jax_cache`` in the
checkout.  Entry points call :func:`use_compilation_cache` once; importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compilation_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
