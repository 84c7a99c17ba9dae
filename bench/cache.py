"""JAX's persistent compilation cache for the benchmark's processes."""

from pathlib import Path

import jax

CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep every program this process compiles in the checkout's fixed
    ``.jax_cache``, so that only a checkout's first run compiles. Call
    before anything compiles. Eviction stays off, whatever the environment
    sets: the cache holds a few programs, and eviction would read an
    access-time file for every entry, which entries written without
    eviction lack."""
    CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
