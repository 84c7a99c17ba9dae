"""Pallas TPU kernel: vectorized lane-split xxHash64.

TPU VPU lanes are 32-bit, so 64-bit hashing runs as uint32 limb arithmetic
(16-bit digit splits for the 32x32->64 partial products). The kernel is pure
VPU work — it exists because placement hashing sits on the insertion critical
path for every shard of every drone (paper §3.4.1) and fuses the
hash + avalanche + modulo pipeline in registers with no HBM round-trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hashing


def _kernel(hi_ref, lo_ref, out_hi_ref, out_lo_ref):
    h = hashing.xxh64_u64((hi_ref[...], lo_ref[...]))
    out_hi_ref[...] = h[0]
    out_lo_ref[...] = h[1]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xxh64(hi: jnp.ndarray, lo: jnp.ndarray, block: int = 1024,
          interpret: bool = False):
    """Batched xxHash64 over (hi, lo) uint32 limb arrays of shape (N,)."""
    n = hi.shape[0]
    pad = (-n) % block
    hi_p = jnp.pad(hi.astype(jnp.uint32), (0, pad)).reshape(-1, block)
    lo_p = jnp.pad(lo.astype(jnp.uint32), (0, pad)).reshape(-1, block)
    rows = hi_p.shape[0]
    out = pl.pallas_call(
        _kernel,
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, block), lambda r: (r, 0)),
                  pl.BlockSpec((1, block), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((1, block), lambda r: (r, 0)),
                   pl.BlockSpec((1, block), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, block), jnp.uint32),
                   jax.ShapeDtypeStruct((rows, block), jnp.uint32)],
        interpret=interpret,
    )(hi_p, lo_p)
    return out[0].reshape(-1)[:n], out[1].reshape(-1)[:n]
