"""Where JAX keeps its persistent compilation cache.

Entry points that compile at full size (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once from their
``main``. Nothing calls it at import time, and the tests never do, so a
library user's process keeps whatever cache setting it had.

The rule: ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache — JAX
reads it itself and this module sets nothing. Otherwise the cache lives
at one fixed path inside the checkout (``.jax_cache``, gitignored). The
path is part of each entry's key, so it never depends on a temporary
name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Apply the rule above and return the cache directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path                 # JAX already reads the variable
    import jax
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
