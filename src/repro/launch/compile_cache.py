"""JAX's persistent compilation cache for the drivers and the chip smoke.

The cache key includes the cache's path, so the directory must not move
between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself) and otherwise ``<checkout>/.jax_cache``.
Called from the drivers' ``run()``, never at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return Path(os.environ[ENV])
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
