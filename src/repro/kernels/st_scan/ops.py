"""Jit'd wrapper for the st_scan Pallas kernel.

Accepts the datastore's NATIVE column-major layout (``(E, 3+V, C)`` tuple
log, ``(E, 2, C)`` shard ids) and the QueryPred struct. The hot path
performs **no relayout**: the only data movement before the kernel is
constant padding — the tuple axis to a ``block_c`` multiple (a no-op for
lane-aligned store capacities), the query axis to a ``block_q`` multiple
(padding queries carry ``sublist_len == 0`` so they match nothing and are
sliced off the outputs), and the OR-list axis to the kernel's entry-chunk
multiple. The per-(query, edge) operands and results are handed to the
kernel edge-major and transposed back to the ``(Q, E)`` / ``(Q, K, E)``
shapes ``scan_engine`` returns.
``interpret=None`` (the default) auto-selects: compiled execution on TPU,
interpret mode elsewhere (CPU tests / this container).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.st_scan.ref import check_channels
from repro.kernels.st_scan.st_scan import L_CHUNK, st_scan_kernel


def pack_pred(pred):
    """QueryPred -> (Q, 8) float32 + (Q, 8) int32 arrays for the kernel."""
    zf = jnp.zeros_like(pred.lat0)
    pred_f = jnp.stack([pred.lat0, pred.lat1, pred.lon0, pred.lon1,
                        pred.t0, pred.t1, zf, zf], axis=-1).astype(jnp.float32)
    zi = jnp.zeros_like(pred.sid_hi)
    pred_i = jnp.stack([pred.sid_hi, pred.sid_lo,
                        pred.has_spatial.astype(jnp.int32),
                        pred.has_temporal.astype(jnp.int32),
                        pred.has_sid.astype(jnp.int32),
                        pred.is_and.astype(jnp.int32), zi, zi], axis=-1)
    return pred_f, pred_i.astype(jnp.int32)


@partial(jax.jit, static_argnames=("block_c", "block_q", "interpret",
                                   "channels", "valid_c"))
def st_scan(tup_f, tup_sid, tup_count, pred, sublists, sublist_len,
            block_c: int = 512, block_q: int = 8,
            interpret: Optional[bool] = None,
            channels: Tuple[int, ...] = (0,),
            valid_c: Optional[int] = None):
    """Drop-in replacement for ref.st_scan_ref backed by the Pallas kernel.

    ``tup_f``/``tup_sid`` are column-major ``(E, 3+V, C)`` / ``(E, 2, C)``
    (the native StoreState layout — nothing is transposed here).
    ``tup_count`` is the monotonic total-written counter; the valid window is
    ``min(count, valid_c)`` where ``valid_c`` is the logical ring capacity
    (None = C) — forwarded to the kernel so neither store lane-padding nor
    this wrapper's block padding is ever admitted. ``channels`` (static)
    selects the sensor channels to aggregate — value rows ``3 + channel`` of
    the log, all fused into one sweep.

    Returns (count, vsum, vmin, vmax): count (Q, E) int32; vsum/vmin/vmax
    (Q, K, E) float32 with K = len(channels).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    e, w, c = tup_f.shape
    value_cols = check_channels(channels, w)
    if valid_c is None:
        valid_c = c
    block_c = min(block_c, max(c, 1))
    pad_c = (-c) % block_c
    if pad_c:
        tup_f = jnp.pad(tup_f, ((0, 0), (0, 0), (0, pad_c)))
        tup_sid = jnp.pad(tup_sid, ((0, 0), (0, 0), (0, pad_c)),
                          constant_values=-1)
    # Pad the query batch to a tile multiple: padding queries are inert
    # (sublist_len == 0 selects no edge) and sliced off below. block_q is
    # NOT shrunk for small batches — a lone query runs as a degenerate
    # block_q-wide tile (same HBM tuple traffic, one compiled variant).
    q = pred.lat0.shape[0]
    pad_q = (-q) % block_q
    pred_f, pred_i = pack_pred(pred)
    if pad_q:
        pred_f = jnp.pad(pred_f, ((0, pad_q), (0, 0)))
        pred_i = jnp.pad(pred_i, ((0, pad_q), (0, 0)))
        sublists = jnp.pad(sublists, ((0, pad_q), (0, 0), (0, 0), (0, 0)),
                           constant_values=-(1 << 30))
        sublist_len = jnp.pad(sublist_len, ((0, pad_q), (0, 0)))
    # Pad the OR-list length to the kernel's entry-chunk multiple (entries
    # past sublist_len never match, so the padding value is irrelevant).
    l = sublists.shape[2]
    pad_l = (-l) % L_CHUNK
    if pad_l:
        sublists = jnp.pad(sublists, ((0, 0), (0, 0), (0, pad_l), (0, 0)),
                           constant_values=-(1 << 30))
    # Kernel layout: per-edge operands and results are edge-major with a
    # trailing unit dim, so no block puts the edge axis in its last two dims.
    count, vsum, vmin, vmax = st_scan_kernel(
        tup_f, tup_sid, tup_count.astype(jnp.int32), pred_f, pred_i,
        sublists, sublist_len.T[:, :, None], block_c=block_c,
        block_q=block_q, interpret=interpret, valid_c=min(valid_c, c),
        value_cols=value_cols)
    count = count[:, :q, 0].T                                  # (Q, E)
    vsum, vmin, vmax = (a[:, :, :q, 0].transpose(2, 1, 0)     # (Q, K, E)
                        for a in (vsum, vmin, vmax))
    return count, vsum, vmin, vmax
