"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile at full size (``chip_smoke.py``, the benchmark
scripts) call :func:`enable_compile_cache` once at start-up; importing this
module changes nothing.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because the path is part of the cache key
and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
