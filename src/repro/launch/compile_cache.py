"""JAX's persistent compilation cache, switched on by the entry points.

A streamed pass compiles one Block-ELL kernel per segment shape, so a cold
process pays every compile again. Entry points (`chip_smoke.py`, the
serve/train launchers, the benchmark drivers) call `enable_compile_cache`
first thing; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache, resolved from this file (src/repro/launch/...), so
# every run from one checkout finds the same entries. Listed in .gitignore.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the directory: JAX reads it
    itself and no other is set here. Otherwise the cache lives at
    `CHECKOUT_CACHE_DIR`. Every program is written, however fast it
    compiled: the per-segment kernels each compile in under JAX's default
    one-second threshold, and they are what a warm run must not repeat.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
