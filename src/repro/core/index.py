"""Distributed in-memory shard index with slicing + retention (paper §3.4.3).

Every edge keeps a fixed-capacity table of index entries
``{shardID, bbox, trange, replicas[3]}``. An entry for a shard is written to
*every* edge owning one of the shard's spatial/temporal slices (over-
replication), so that any overlapping range query — which slices its own
predicate with the same grid — finds the shard on at least one lookup edge.

Static-shape storage (TPU adaptation):
  ent_f:  (E, CAP, 6)  float32  lat0, lat1, lon0, lon1, t0, t1
  ent_i:  (E, CAP, 5)  int32    sid_hi, sid_lo, r0, r1, r2
  valid:  (E, CAP)     bool
  cursor: (E,)         int32    append position
  dropped:(E,)         int32    entries lost to capacity overflow (telemetry)
  retired:(E,)         int32    entries invalidated by retention or repair
                                entry reclamation (telemetry)
  ent_step:(E, CAP)    int32    ingest step that wrote the entry (epoch clock
                                for the incremental-repair outage windows —
                                see ``core/repair.py``)

Retention (sustained ingest): the tuple log is a ring buffer, so an edge only
retains a sliding window of recent tuples. ``retire_entries`` invalidates
entries whose newest timestamp (t1) has fallen behind the per-edge retention
watermark — their tuples have been overwritten and a lookup hit would only
produce an empty sub-query. ``compact_index`` then squashes the surviving
entries to the front of the table so the append cursor is reusable; together
they keep the index serving indefinitely instead of saturating at CAP. The
datastore wires both into ``insert_step`` on a configurable cadence
(``StoreConfig.retention_every``).

The leading E axis is the *logical edge axis* — sharded over the device mesh
by the datastore; every operation here is batched dense array math so the
whole index is pjit-compatible.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.placement import ShardMeta


class IndexState(NamedTuple):
    ent_f: jnp.ndarray
    ent_i: jnp.ndarray
    valid: jnp.ndarray
    cursor: jnp.ndarray
    dropped: jnp.ndarray
    retired: jnp.ndarray
    ent_step: jnp.ndarray


class QueryPred(NamedTuple):
    """A spatio-temporal query predicate (paper Fig 6).

    ``has_*`` flags select which filters participate; ``is_and`` picks the
    boolean combination (§3.5.1). All fields are batched (Q,).
    """
    lat0: jnp.ndarray
    lat1: jnp.ndarray
    lon0: jnp.ndarray
    lon1: jnp.ndarray
    t0: jnp.ndarray
    t1: jnp.ndarray
    sid_hi: jnp.ndarray
    sid_lo: jnp.ndarray
    has_spatial: jnp.ndarray   # bool
    has_temporal: jnp.ndarray  # bool
    has_sid: jnp.ndarray       # bool
    is_and: jnp.ndarray        # bool


class MatchedShards(NamedTuple):
    """Index-lookup result: the shards a query must touch (paper §3.5.1)."""
    sid_hi: jnp.ndarray    # (Q, S)
    sid_lo: jnp.ndarray    # (Q, S)
    replicas: jnp.ndarray  # (Q, S, 3)
    valid: jnp.ndarray     # (Q, S)
    overflow: jnp.ndarray  # (Q,) — more than S distinct shards matched


def init_index(n_edges: int, capacity: int) -> IndexState:
    return IndexState(
        ent_f=jnp.zeros((n_edges, capacity, 6), jnp.float32),
        ent_i=jnp.full((n_edges, capacity, 5), -1, jnp.int32),
        valid=jnp.zeros((n_edges, capacity), jnp.bool_),
        cursor=jnp.zeros((n_edges,), jnp.int32),
        dropped=jnp.zeros((n_edges,), jnp.int32),
        retired=jnp.zeros((n_edges,), jnp.int32),
        ent_step=jnp.zeros((n_edges, capacity), jnp.int32),
    )


def insert_entries(state: IndexState, meta: ShardMeta, replicas: jnp.ndarray,
                   edge_mask: jnp.ndarray, step: jnp.ndarray = 0) -> IndexState:
    """Write index entries for B shards onto all edges in their slice mask.

    Args:
      meta:      ShardMeta of B shards.
      replicas:  (B, 3) replica edges.
      edge_mask: (B, E) bool — edges that must index each shard (slice owners
                 plus the replica edges themselves).
      step:      scalar int32 — the store's ingest step performing the write,
                 recorded per entry in ``ent_step`` (the epoch clock the
                 incremental repair sweep keys outage windows against).
    """
    e, cap = state.valid.shape
    b = edge_mask.shape[0]
    # Append position of shard b on edge e: cursor[e] + (rank of b among
    # shards targeting e). Dense cumsum keeps this scatter-free until the end.
    rank = jnp.cumsum(edge_mask, axis=0) - 1                      # (B, E)
    pos = state.cursor[None, :] + rank                            # (B, E)
    ok = edge_mask & (pos < cap)
    n_dropped = jnp.sum(edge_mask & (pos >= cap), axis=0)

    ee = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32), (b, e))
    # Out-of-bounds rows are dropped by scatter mode='drop'.
    pp = jnp.where(ok, pos, cap)

    vals_f = jnp.stack([meta.lat0, meta.lat1, meta.lon0, meta.lon1,
                        meta.t0, meta.t1], axis=-1)               # (B, 6)
    vals_i = jnp.concatenate([meta.sid_hi[:, None], meta.sid_lo[:, None],
                              replicas.astype(jnp.int32)], axis=-1)  # (B, 5)
    vals_f = jnp.broadcast_to(vals_f[:, None, :], (b, e, 6))
    vals_i = jnp.broadcast_to(vals_i[:, None, :], (b, e, 5))

    ent_f = state.ent_f.at[ee, pp].set(vals_f, mode="drop")
    ent_i = state.ent_i.at[ee, pp].set(vals_i, mode="drop")
    valid = state.valid.at[ee, pp].set(ok, mode="drop")
    steps = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (b, e))
    ent_step = state.ent_step.at[ee, pp].set(steps, mode="drop")
    cursor = jnp.minimum(state.cursor + jnp.sum(edge_mask, axis=0), cap).astype(jnp.int32)
    return IndexState(ent_f, ent_i, valid, cursor, state.dropped + n_dropped,
                      state.retired, ent_step)


def retire_entries(state: IndexState, t_watermark: jnp.ndarray) -> IndexState:
    """Invalidate entries whose tuples have left the retention window.

    Args:
      t_watermark: (E,) float32 — per-edge oldest retained tuple timestamp
          (``-inf`` until that edge's ring buffer has wrapped).

    An entry's data lives on its *replica* edges (``ent_i[..., 2:5]``), not on
    the slice-owner edge holding the entry, so the test is replica-aware: an
    entry is retired only when its newest timestamp ``t1`` is behind the
    watermark of **every** replica edge — every tuple of the shard has
    t <= t1 < watermark[r] <= all timestamps retained on replica r, i.e. the
    shard is gone from everywhere it was stored. Entries whose data may
    survive on a slower replica edge are kept. Keeping a stale entry costs
    occupancy, not result quality: a fully-overwritten shard's id matches no
    tuple (empty sub-query), a partially-overwritten one still surfaces its
    surviving tuples. Exactness guarantees are scoped to query windows
    retained on every replica — see the retention notes in ``datastore.py``.
    """
    reps = state.ent_i[..., 2:5]                                  # (E, CAP, 3)
    rep_wm = t_watermark[jnp.clip(reps, 0, t_watermark.shape[0] - 1)]
    rep_wm = jnp.where(reps >= 0, rep_wm, jnp.inf)                # unused slots
    gone_everywhere = state.ent_f[..., 5] < jnp.min(rep_wm, axis=-1)
    stale = state.valid & gone_everywhere
    return state._replace(
        valid=state.valid & ~stale,
        retired=state.retired + jnp.sum(stale, axis=1).astype(jnp.int32))


def compact_index(state: IndexState) -> IndexState:
    """Squash valid entries to the front of each edge's table (stable order)
    and rewind the append cursor, making slots freed by ``retire_entries``
    writable again. Pure fixed-shape gather — jit/pjit compatible."""
    order = jnp.argsort(~state.valid, axis=1, stable=True)   # valid-first
    ent_f = jnp.take_along_axis(state.ent_f, order[..., None], axis=1)
    ent_i = jnp.take_along_axis(state.ent_i, order[..., None], axis=1)
    valid = jnp.take_along_axis(state.valid, order, axis=1)
    ent_step = jnp.take_along_axis(state.ent_step, order, axis=1)
    cursor = jnp.sum(state.valid, axis=1).astype(jnp.int32)
    return IndexState(ent_f, ent_i, valid, cursor, state.dropped, state.retired,
                      ent_step)


def entry_matches(state: IndexState, pred: QueryPred) -> jnp.ndarray:
    """(Q, E, CAP) bool — which index entries satisfy each query predicate."""
    f = state.ent_f  # (E, CAP, 6)
    i = state.ent_i
    def bc(x):  # (Q,) -> (Q, 1, 1)
        return x[:, None, None]
    sp = ~((bc(pred.lat1) < f[None, :, :, 0]) | (f[None, :, :, 1] < bc(pred.lat0)) |
           (bc(pred.lon1) < f[None, :, :, 2]) | (f[None, :, :, 3] < bc(pred.lon0)))
    tp = ~((bc(pred.t1) < f[None, :, :, 4]) | (f[None, :, :, 5] < bc(pred.t0)))
    ip = (i[None, :, :, 0] == bc(pred.sid_hi)) & (i[None, :, :, 1] == bc(pred.sid_lo))
    hs, ht, hi = bc(pred.has_spatial), bc(pred.has_temporal), bc(pred.has_sid)
    is_and = bc(pred.is_and)
    m_and = (sp | ~hs) & (tp | ~ht) & (ip | ~hi)
    m_or = (sp & hs) | (tp & ht) | (ip & hi)
    return jnp.where(is_and, m_and, m_or) & state.valid[None]


def dedup_matched(matched: jnp.ndarray, sid_hi: jnp.ndarray, sid_lo: jnp.ndarray,
                  replicas: jnp.ndarray, max_shards: int) -> MatchedShards:
    """Deduplicate candidate shard ids, batched over queries.

    Sorts matched-first by (sid_hi, sid_lo), keeps the first occurrence of
    each distinct sid, and compacts the distinct matches to the front (so the
    valid slots hold the ``max_shards`` smallest distinct sids in ascending
    order — a canonical form). ``overflow`` flags queries with more distinct
    matches than fit.

    Used by ``lookup`` over the whole index, and by the federated runtime to
    merge per-device candidate lists: because the valid slots are the
    lexicographically smallest distinct sids, merging each device's local
    top-``max_shards`` and re-deduplicating yields exactly the single-device
    result (any sid excluded from a local top list has >= max_shards smaller
    sids locally, hence globally — the distributed top-k argument).

    Args:
      matched:  (Q, N) bool — candidate participates.
      sid_hi:   (Q, N) int32.
      sid_lo:   (Q, N) int32.
      replicas: (Q, N, 3) int32.
    """
    # The sorted columns come out of the sorts themselves: no gather runs
    # over all N candidates, and the replica columns are gathered only at the
    # max_shards selected positions. The stable sorts with the original index
    # as the last operand keep each sid's first occurrence in flat order.
    def one_query(m, hi, lo, rep):
        iota = jnp.arange(m.shape[0], dtype=jnp.int32)
        nm_s, hi_s, lo_s, perm = jax.lax.sort((~m, hi, lo, iota), num_keys=3,
                                              is_stable=True)
        m_s = ~nm_s
        prev_same = jnp.concatenate([jnp.array([False]),
                                     (hi_s[1:] == hi_s[:-1]) & (lo_s[1:] == lo_s[:-1]) & m_s[:-1]])
        is_new = m_s & ~prev_same
        n_unique = jnp.sum(is_new)
        _, *sel = jax.lax.sort((~is_new, hi_s, lo_s, perm), num_keys=1,
                               is_stable=True)
        hi_sel, lo_sel, perm_sel = (x[:max_shards] for x in sel)
        valid = jnp.arange(hi_sel.shape[0]) < n_unique
        return (hi_sel, lo_sel, rep[perm_sel], valid, n_unique > max_shards)

    hi2, lo2, rep2, val2, ovf = jax.vmap(one_query)(matched, sid_hi, sid_lo,
                                                    replicas)
    return MatchedShards(hi2, lo2, rep2, val2, ovf)


def match_candidates(state: IndexState, pred: QueryPred,
                     lookup_mask: jnp.ndarray):
    """Flatten this index slice's entries into per-query candidate lists for
    ``dedup_matched``: (matched, sid_hi, sid_lo, replicas), each (Q, E*CAP).
    ``state`` may be a shard-local slice of the edge axis; ``lookup_mask`` is
    (Q, E_local) over the same slice."""
    q = pred.lat0.shape[0]
    e, cap = state.valid.shape
    match = entry_matches(state, pred) & lookup_mask[:, :, None]   # (Q, E, CAP)
    flat_m = match.reshape(q, e * cap)
    sid_hi = jnp.broadcast_to(state.ent_i[None, :, :, 0], (q, e, cap)).reshape(q, -1)
    sid_lo = jnp.broadcast_to(state.ent_i[None, :, :, 1], (q, e, cap)).reshape(q, -1)
    reps = jnp.broadcast_to(state.ent_i[None, :, :, 2:5], (q, e, cap, 3)).reshape(q, -1, 3)
    return flat_m, sid_hi, sid_lo, reps


def lookup(state: IndexState, pred: QueryPred, lookup_mask: jnp.ndarray,
           max_shards: int) -> MatchedShards:
    """Index lookup (paper §3.5.1): match entries on the selected lookup
    edges, deduplicate shard ids across edges, return up to ``max_shards``.

    Args:
      lookup_mask: (Q, E) bool — edges whose index each query consults.
    """
    flat_m, sid_hi, sid_lo, reps = match_candidates(state, pred, lookup_mask)
    return dedup_matched(flat_m, sid_hi, sid_lo, reps, max_shards)
