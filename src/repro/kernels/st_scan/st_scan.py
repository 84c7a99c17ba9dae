"""Pallas TPU kernel: blocked spatio-temporal predicate scan + aggregation.

This is the per-edge query engine hot loop (the paper's InfluxDB role,
§3.5.2, Fig 5). For each (edge, query-tile) pair the kernel streams the
edge's tuple log through VMEM in ``block_c``-tuple tiles, evaluates the
spatio-temporal predicate and the shard-id OR-list membership of a whole
``block_q``-query tile entirely in vector registers, and accumulates
count/sum/min/max — for a static tuple of sensor channels at once — into
revisited output tiles.

TPU-native layout decisions (vs the paper's row-store in InfluxDB):
  * the tuple log is stored column-major (E, W, C) — NATIVELY, in
    ``StoreState`` itself — so the *tuple* axis is the lane dimension
    (128-aligned by ``init_store``'s capacity padding), giving unit-stride
    vector loads per field with no per-query relayout;
  * queries are tiled: the predicate is a (block_q, block_c) broadcast
    evaluation, so each resident VMEM tuple tile answers block_q queries
    before the grid advances — HBM tuple traffic is ceil(Q/block_q)x the
    log instead of Qx;
  * the shard OR-list keeps its (L, 2) entries on the sublane axis, so each
    query's membership test is an (l_chunk, block_c) compare of list
    columns against the tile's sid rows, OR-folded over ``l_chunk``-entry
    chunks — the working set stays a few dozen vregs whatever L is;
  * aggregation is fused across channels: one predicate mask drives the
    count and every requested channel's sum/min/max accumulators
    (the marginal cost per extra channel is one VMEM row already resident
    in the tuple tile);
  * accumulators are (1, block_q, 1) / (1, K, block_q, 1) output tiles of
    edge-major ``(E, Q, 1)`` / ``(E, K, Q, 1)`` arrays, revisited across the
    c-grid (Pallas revisiting-output pattern), so no cross-block reduction
    pass. The edge axis is leading, never one of the last two block dims,
    which is what Mosaic's (8, 128) block rule requires.

The ring-buffer counter ``tup_count`` is a scalar-prefetch operand (SMEM):
one int per edge, read as a scalar for the validity bound.

Grid note: the grid is ``(E, Q // block_q, C // block_c)`` with the c axis
FASTEST — each (edge, query-tile) accumulator is completed over consecutive
grid steps before the grid moves on (the only ordering under which Pallas
revisited outputs are well-defined), and the tuple-tile index map depends
only on (e, c), so one fetch of the log serves the whole query tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# OR-list entries compared per inner-loop step: an (L_CHUNK, block_c) int32
# compare is 16 vregs at block_c = 512.
L_CHUNK = 32


def _kernel(cnt_ref, tupf_ref, sidl_ref, predf_ref, predi_ref, subl_ref,
            slen_ref, count_ref, vsum_ref, vmin_ref, vmax_ref, *,
            block_c: int, valid_c: int, value_cols: tuple):
    pe = pl.program_id(0)
    pc = pl.program_id(2)

    @pl.when(pc == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        vsum_ref[...] = jnp.zeros_like(vsum_ref)
        vmin_ref[...] = jnp.full_like(vmin_ref, jnp.inf)
        vmax_ref[...] = jnp.full_like(vmax_ref, -jnp.inf)

    t = tupf_ref[0, 0:1, :]      # (1, BC)
    lat = tupf_ref[0, 1:2, :]
    lon = tupf_ref[0, 2:3, :]
    sid_hi = sidl_ref[0, 0:1, :]
    sid_lo = sidl_ref[0, 1:2, :]

    # Ring-buffer validity: slots below min(count, valid_c) are live, where
    # valid_c is the LOGICAL ring capacity — a monotonic total-written count
    # above capacity must never admit lane-padding slots.
    n_valid = jnp.minimum(cnt_ref[pe], valid_c)
    base = pc * block_c
    idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
    alive = idx < n_valid        # (1, BC)

    pf = predf_ref[...]          # (BQ, 8) lat0, lat1, lon0, lon1, t0, t1, -, -
    pi = predi_ref[...]          # (BQ, 8) sid_hi, sid_lo, has_s, has_t, has_i, is_and
    sp = (pf[:, 0:1] <= lat) & (lat <= pf[:, 1:2]) & \
         (pf[:, 2:3] <= lon) & (lon <= pf[:, 3:4])            # (BQ, BC)
    tp = (pf[:, 4:5] <= t) & (t <= pf[:, 5:6])
    ip = (sid_hi == pi[:, 0:1]) & (sid_lo == pi[:, 1:2])
    hs, ht, hi = pi[:, 2:3] != 0, pi[:, 3:4] != 0, pi[:, 4:5] != 0
    m_and = (sp | ~hs) & (tp | ~ht) & (ip | ~hi)
    m_or = (sp & hs) | (tp & ht) | (ip & hi)
    is_and = pi[:, 5:6] != 0      # bool selects as logic: Mosaic has no i1 select
    pm = (is_and & m_and) | (~is_and & m_or)                  # (BQ, BC)

    # Shard OR-list membership, one query row at a time: list entries sit on
    # sublanes, tuple sids on lanes; entries at or past |sublist_len| never
    # match.
    slen = slen_ref[0]                                        # (BQ, 1)
    block_q = slen.shape[0]
    n_chunks = subl_ref.shape[2] // L_CHUNK
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    entry = jax.lax.broadcasted_iota(jnp.int32, (L_CHUNK, 1), 0)
    in_list = jnp.zeros((block_q, block_c), jnp.bool_)
    for qi in range(block_q):
        n_entries = jnp.abs(slen[qi:qi + 1, :])                # (1, 1)

        def chunk(j, acc, qi=qi, n_entries=n_entries):
            k0 = pl.multiple_of(j * L_CHUNK, L_CHUNK)
            l_hi = subl_ref[qi, 0, pl.ds(k0, L_CHUNK), 0:1]   # (LC, 1)
            l_lo = subl_ref[qi, 0, pl.ds(k0, L_CHUNK), 1:2]
            ok = (k0 + entry) < n_entries                     # (LC, 1)
            hit = (sid_hi == l_hi) & (sid_lo == l_lo) & ok    # (LC, BC)
            return acc | hit.astype(jnp.int32)

        acc = jax.lax.fori_loop(0, n_chunks, chunk,
                                jnp.zeros((L_CHUNK, block_c), jnp.int32))
        hit_q = jnp.max(acc, axis=0, keepdims=True) > 0       # (1, BC)
        in_list = in_list | ((rows == qi) & hit_q)
    shard_ok = ((slen < 0) | in_list) & (slen != 0)

    m = pm & shard_ok & alive                                 # (BQ, BC)
    count_ref[0] += jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)
    # Fused multi-channel aggregation: the mask is computed once; every
    # requested channel's row is already resident in the VMEM tuple tile.
    for kk, col in enumerate(value_cols):
        v = tupf_ref[0, col:col + 1, :]                       # (1, BC)
        vsum_ref[0, kk] += jnp.sum(jnp.where(m, v, 0.0), axis=1, keepdims=True)
        vmin_ref[0, kk] = jnp.minimum(
            vmin_ref[0, kk],
            jnp.min(jnp.where(m, v, jnp.inf), axis=1, keepdims=True))
        vmax_ref[0, kk] = jnp.maximum(
            vmax_ref[0, kk],
            jnp.max(jnp.where(m, v, -jnp.inf), axis=1, keepdims=True))


def st_scan_kernel(tupf_t, sid_t, tup_count, pred_f, pred_i, sublists,
                   slen_t, *, block_c: int = 512, block_q: int = 8,
                   interpret: bool = False,
                   valid_c: "int | None" = None,
                   value_cols: "tuple[int, ...]" = (3,)):
    """Invoke the Pallas scan on kernel-layout operands (``ops.st_scan``
    adapts the engine's ``(Q, E)`` layout to these).

    Args:
      tupf_t:      (E, W, C) float32 column-major tuple log (W >= 4).
      sid_t:       (E, 2, C) int32 shard ids.
      tup_count:   (E,) int32 — ring-buffer total-written counter; clamped
                   in-kernel to min(count, valid_c). Scalar-prefetched.
      pred_f:      (Q, 8) float32 packed predicate; Q % block_q == 0.
      pred_i:      (Q, 8) int32 packed predicate.
      sublists:    (Q, E, L, 2) int32 OR-lists; L % L_CHUNK == 0.
      slen_t:      (E, Q, 1) int32 OR-list lengths (edge-major).
      block_q:     queries evaluated per resident tuple tile — the HBM
                   tuple-traffic divisor for batched queries. Compiled
                   kernels need a multiple of 8 (sublane tiling).
      interpret:   run the Pallas interpreter instead of compiling.
      valid_c:     logical ring capacity (ops.py forwards the store's
                   un-lane-padded capacity so padding lanes are never
                   admitted); None = C.
      value_cols:  static rows of the column-major log to aggregate (the
                   selected sensor channels; 3 = v0). All are accumulated in
                   the same sweep.

    Returns (count, vsum, vmin, vmax), edge-major: count (E, Q, 1) int32;
    the rest (E, K, Q, 1) float32 with K = len(value_cols).
    """
    e, w, c = tupf_t.shape
    if valid_c is None:
        valid_c = c
    n_ch = len(value_cols)
    for col in value_cols:
        if not 3 <= col < w:
            raise ValueError(
                f"value_col={col} out of range: the column-major log has "
                f"rows 0..2 = (t, lat, lon) and value rows 3..{w - 1}.")
    q = pred_f.shape[0]
    l = sublists.shape[2]
    if c % block_c:
        raise ValueError(f"C={c} must be a multiple of block_c={block_c}")
    if q % block_q:
        raise ValueError(f"Q={q} must be a multiple of block_q={block_q} "
                         "(ops.py pads the query batch)")
    if l % L_CHUNK:
        raise ValueError(f"L={l} must be a multiple of {L_CHUNK} "
                         "(ops.py pads the OR-lists)")
    grid = (e, q // block_q, c // block_c)

    kernel = functools.partial(_kernel, block_c=block_c, valid_c=valid_c,
                               value_cols=tuple(value_cols))
    acc_spec = pl.BlockSpec((1, n_ch, block_q, 1),
                            lambda e_, q_, c_, cnt: (e_, 0, q_, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, w, block_c),
                             lambda e_, q_, c_, cnt: (e_, 0, c_)),
                pl.BlockSpec((1, 2, block_c),
                             lambda e_, q_, c_, cnt: (e_, 0, c_)),
                pl.BlockSpec((block_q, 8), lambda e_, q_, c_, cnt: (q_, 0)),
                pl.BlockSpec((block_q, 8), lambda e_, q_, c_, cnt: (q_, 0)),
                pl.BlockSpec((block_q, 1, l, 2),
                             lambda e_, q_, c_, cnt: (q_, e_, 0, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda e_, q_, c_, cnt: (e_, q_, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, 1),
                             lambda e_, q_, c_, cnt: (e_, q_, 0)),
                acc_spec, acc_spec, acc_spec,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((e, q, 1), jnp.int32),
            jax.ShapeDtypeStruct((e, n_ch, q, 1), jnp.float32),
            jax.ShapeDtypeStruct((e, n_ch, q, 1), jnp.float32),
            jax.ShapeDtypeStruct((e, n_ch, q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="st_scan",
    )(tup_count, tupf_t, sid_t, pred_f, pred_i, sublists, slen_t)
    return tuple(out)
