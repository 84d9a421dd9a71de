"""Persistent XLA compilation cache.

The fused transform programs take minutes to compile at large N; the
persistent cache makes that a once-per-cache-directory cost instead of
once-per-process. The directory is part of what JAX matches on, so it
never moves: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compilation_cache"]

# <checkout>/.jax_cache (gitignored): this file is
# <checkout>/swiftly_tpu/utils/cache.py
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compilation_cache(min_compile_secs=1.0):
    """Cache compiled XLA executables on disk across processes.

    :param min_compile_secs: only cache programs that took at least this
        long to compile
    :returns: the cache directory in use
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_secs)
    )
    return cache_dir
