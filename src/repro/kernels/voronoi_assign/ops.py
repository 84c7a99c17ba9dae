"""Jit'd wrapper for voronoi_assign (compiled unless interpret=True)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.voronoi_assign.voronoi_assign import voronoi_assign


def hash_spatial_kernel(lat: jnp.ndarray, lon: jnp.ndarray,
                        sites: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Kernel-backed H_s: (lat, lon) -> edge index."""
    pts = jnp.stack([lat.reshape(-1), lon.reshape(-1)], axis=-1)
    return voronoi_assign(pts, sites, interpret=interpret).reshape(lat.shape)
