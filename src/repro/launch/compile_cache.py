"""JAX persistent compilation cache for the simulator's entry points.

The engines pad their inputs to bucketed shapes (``cache._bucket_len``,
``stack._pad_len``, ``dram._chunk_bucket_len``), so a run compiles a few
dozen small programs, most in under a second. Caching them on disk lets the
next process on the same machine skip those compiles.

Entry points (``chip_smoke.py``, ``python -m repro.launch.simulate``,
``benchmarks/dse_sweep.py``) call ``enable_compile_cache()`` from their
``main``; importing the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, since the path is part of the key.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Cache every compiled program of this process; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and JAX reads
    it itself. Otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    # Bucketed shapes compile in well under JAX's 1 s default threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
