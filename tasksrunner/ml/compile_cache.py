"""JAX's persistent compilation cache, placed from outside or fixed.

A cold start compiles every serving bucket (and the bench's train
step); the persistent cache lets the next process on the same machine
skip that. Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives
there and nowhere else. Otherwise it lives at one fixed directory in
the checkout, resolved from this package's location rather than the
working directory: the path is part of the cache's key, so a directory
that moves from run to run never hits.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed fallback, gitignored
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Call before the first compile."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
