"""Where JAX keeps its persistent compilation cache.

The cache is keyed by the directory it lives in, so it must be a fixed
path to ever hit: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and no other cache is configured here),
else ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
