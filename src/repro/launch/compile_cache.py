"""JAX's persistent compilation cache, switched on by every entry point.

Each entry point (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks.run``, ``chip_smoke.py``) calls ``enable_compile_cache()``
first thing in ``main()``; importing a module never sets a cache.  A
process that compiles a 48-layer decode step it compiled before then
reads it back instead.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path in the checkout (the path is part of the cache key, so it
#: must not move between runs)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: jax reads
    it itself and no other is set.  Otherwise the cache is
    ``artifacts/jax_cache/`` in the checkout."""
    import jax
    # cache every program, however quick to compile: a fresh machine
    # starts cold, and the eager ops of a model's init alone add up to
    # tens of seconds of compiling on the chip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # key on the program's metadata too: jax strips it from the key by
    # default, so a program that differs from a cached one only in its
    # named scopes would load the cached executable, whose device ops
    # then carry the old scopes (or none) in a profiler trace
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
