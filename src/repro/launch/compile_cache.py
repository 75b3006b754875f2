"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`use_compile_cache` once, before their
first compile; libraries and tests never do.  ``JAX_COMPILATION_CACHE_DIR``,
when set, rules: JAX reads it itself and nothing here overrides it.
Otherwise the cache goes to ``.jax_cache/`` at the root of the checkout.
The directory is fixed, never derived from a temporary name, a pid or
the clock, so a second run of the same checkout finds what the first
one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Every program is kept, however fast it compiled: a cold run on the
    chip compiles dozens of small tick programs besides the large ones.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
