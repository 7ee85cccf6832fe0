"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
module sets no other directory.  Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, never built from a temporary name, a
pid or the time, so a later process on the same checkout finds what an
earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Call before the first compile.  Every program is cached, however quick
    its compile: the Pallas kernels compile in well under JAX's default
    one-second floor, and a cold process would otherwise rebuild them all.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
