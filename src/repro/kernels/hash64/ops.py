"""Jit'd wrapper for the hash64 kernel (compiled unless interpret=True)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.hash64.hash64 import xxh64


def xxh64_mod(hi: jnp.ndarray, lo: jnp.ndarray, n_edges: int,
              interpret: bool = False) -> jnp.ndarray:
    """H_i-style placement hash: xxh64(key) mod n_edges, int32."""
    out_hi, out_lo = xxh64(hi, lo, interpret=interpret)
    from repro.core.hashing import mod_u64
    return mod_u64((out_hi, out_lo), n_edges)
