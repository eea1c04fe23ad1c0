"""Persistent compilation cache for the programs that drive the chip.

Entry points (``chip_smoke.py``, ``benchmarks.run``,
``benchmarks.kernel_bench``) call ``enable_compile_cache`` once at start;
the library never does, so importing ``repro`` changes no JAX config.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed, git-ignored directory at the root of the checkout.  The path
#: is fixed so that a later run of the same checkout finds the entries.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    ``DEFAULT_DIR``.  Every program is cached, however fast it compiled:
    the update kernels compile in about a second each, under JAX's
    default one-second threshold.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
