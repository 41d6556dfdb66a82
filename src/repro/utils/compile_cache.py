"""Where JAX keeps its persistent compilation cache.

`JAX_COMPILATION_CACHE_DIR`, when set, places the cache from outside:
JAX reads it itself and nothing here overrides it.  Otherwise the cache
lives at `<checkout>/.jax_cache`, a fixed path resolved from this file:
the directory is part of what a run is keyed on, so it holds no
temporary name, process id or time, and a second run of the same
program finds what the first compiled.  Entry points (`chip_smoke.py`,
`repro.launch.train`, `repro.launch.serve`) call `enable_compile_cache`
before their first compile; tests never do.
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
