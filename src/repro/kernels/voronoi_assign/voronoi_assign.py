"""Pallas TPU kernel: Voronoi point-location as MXU nearest-site search.

H_s point-location (paper §3.4.1) = nearest site over E edges. The kernel
computes the distance matrix for a block of points via the matmul expansion
``||p-s||^2 = ||p||^2 - 2 p.s + ||s||^2`` (the ||p||^2 term is argmin-
invariant and dropped), so the inner loop is a (BP, 2) x (2, E) dot_general on
the MXU followed by a lane-wise argmin. Points are stored coordinate-major
(2, N) so point blocks load with unit stride on the lane axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(pts_ref, sites_ref, snorm_ref, out_ref):
    pts = pts_ref[...]                 # (2, BP)
    sites = sites_ref[...]             # (2, E)
    snorm = snorm_ref[...]             # (1, E)
    # dist (BP, E) = snorm - 2 * pts^T sites  (MXU contraction over coord dim)
    cross = jax.lax.dot_general(pts, sites, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (BP, E)
    dist = snorm - 2.0 * cross
    out_ref[...] = jnp.argmin(dist, axis=1).astype(jnp.int32)[None, :]


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def voronoi_assign(points: jnp.ndarray, sites: jnp.ndarray,
                   block_p: int = 1024, interpret: bool = False) -> jnp.ndarray:
    """(N, 2) float points x (E, 2) sites -> (N,) int32 nearest site."""
    n = points.shape[0]
    e = sites.shape[0]
    pad = (-n) % block_p
    # Center on the site centroid: argmin-invariant, but essential for fp32
    # accuracy with raw geographic coordinates (see core/voronoi.py).
    c = jnp.mean(sites.astype(jnp.float32), axis=0)
    pts_t = jnp.pad(points.astype(jnp.float32) - c, ((0, pad), (0, 0))).T  # (2, N+pad)
    sites_t = (sites.astype(jnp.float32) - c).T                            # (2, E)
    snorm = jnp.sum(sites_t * sites_t, axis=0, keepdims=True)          # (1, E)
    rows = pts_t.shape[1] // block_p
    out = pl.pallas_call(
        _kernel,
        grid=(rows,),
        in_specs=[pl.BlockSpec((2, block_p), lambda r: (0, r)),
                  pl.BlockSpec((2, e), lambda r: (0, 0)),
                  pl.BlockSpec((1, e), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((1, block_p), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block_p), jnp.int32),
        interpret=interpret,
    )(pts_t, sites_t, snorm)
    return out.reshape(-1)[:n]
