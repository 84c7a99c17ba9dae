"""Persistent XLA compilation cache placement for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` before their first compile, so repeated runs
from one checkout reuse compiled programs. The cache directory is part of
the cache's key, so it must not move between runs: it is either the
directory the environment names in ``JAX_COMPILATION_CACHE_DIR`` (which JAX
reads itself; nothing is set in code then) or the fixed, gitignored
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
