"""Where JAX keeps its persistent compilation cache.

A cold TPU compile takes seconds, so every process that compiles for
the chip (the verdict service, the daemon, ``chip_smoke.py``) places
the cache before its first compile.  The path is part of the cache's
key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that itself), else ``.jax_cache/`` at
the checkout root — never a temp, pid- or time-derived directory.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the compile cache (idempotent); returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
