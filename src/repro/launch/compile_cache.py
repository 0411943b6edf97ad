"""JAX's persistent compilation cache, placeable from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmarks)
call `use_compile_cache` before their first compile.  Where the
environment sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads that directory
itself and nothing here overrides it.  Otherwise the cache lives at a fixed
path inside the checkout, ``<checkout>/.jax_cache`` (git-ignored), so a
later run from the same place finds what an earlier one compiled: the path
never depends on a temporary name, the process or the time.

The test suite keeps the cache off (``tests/conftest.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the
    checkout's ``.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
