"""Where JAX's persistent compilation cache lives, decided in one place.

The entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) call ``enable_compile_cache()`` before their first
compile; library code and tests never do.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache stays
  there; no other directory is set in code.
* Otherwise: ``<checkout>/.jax_cache``.  The path is fixed (never built from
  a temp name, a pid or the time) because it is part of what a later run
  must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
