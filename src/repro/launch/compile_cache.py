"""Where JAX keeps its persistent compilation cache: one decision, made
here, for every entry point (the serving and training CLIs, the benchmark
runner and `chip_smoke.py`)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout (listed in .gitignore): a cache only
# pays when a later process looks where an earlier one wrote
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to `CHECKOUT_CACHE`.
    Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
