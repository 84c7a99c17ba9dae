"""AerialDB datastore: federated insert and decentralized query (paper §3).

State layout — every array carries the *logical edge axis* E in front, which
the launcher shards over the device mesh (edges ≈ experts in an MoE: the
insertion path literally reuses the dispatch-by-one-hot pattern). All
operations are pure jittable functions: ``insert_step(state, shards) ->
(state, info)`` and ``query_step(state, queries) -> (results, info)``.

Sharded-state layout contract (the federation story, paper §3.3): the leading
E dimension of every ``StoreState`` array (including the nested ``IndexState``)
is the mesh axis ``"edge"`` — each device of an ``("edge",)`` mesh hosts a
contiguous block of ``E / n_devices`` ground edge servers, exactly like one
edge site owning its local InfluxDB. The bodies here are therefore factored as
*shard-local* functions (``insert_local`` / ``query_local``) parameterized by
``edge_ids`` — the global ids of the edges this state slice holds — plus a
collective hook for the two metadata-scale cross-device exchanges (the
retention-watermark all-gather and the candidate-shard merge).
``insert_step``/``query_step`` are the 1-device special case
(``edge_ids = arange(E)``, identity hooks); ``repro.distributed.federation``
wraps the same bodies in ``shard_map`` so the per-edge tuple scan runs
device-local and only the final (Q, E) combine crosses devices.

  tup_f:   (E, 3+V, CAP_L) float32   COLUMN-MAJOR tuple log: row r of edge e
                                     is field r (t, lat, lon, v0..) over all
                                     log slots — the tuple axis is LAST
  tup_sid: (E, 2, CAP_L)   int32     owning shard id rows (hi, lo)
  tup_count: (E,)          int32     total tuples EVER written (monotonic)
  tup_pos: (E,)            int32     ring write cursor in [0, capacity)
  tup_overwritten, tup_dropped: (E,) retention / loss telemetry
  index:   IndexState                sliced distributed index (index.py)

Column-major log layout (the scan-engine contract): the tuple axis is the
*minor* (lane) dimension, sized ``CAP_L = StoreConfig.padded_capacity`` — the
logical ``tuple_capacity`` rounded up to a 128-lane multiple at
``init_store``. Queries therefore stream each field as unit-stride
128-aligned vector loads with **no relayout and no padding at query time**;
the cost moved to the insert path, whose scatter writes one *column* (all
3+V+2 field rows of a slot) per tuple instead of one contiguous row — a
strided write of a few words per tuple, amortized far below the one-hot
dispatch that surrounds it. Lane-padding slots in
``[tuple_capacity, padded_capacity)`` are never written and never admitted:
ring positions are taken modulo the LOGICAL capacity, and both scan engines
clamp validity to ``slot < min(tup_count, tuple_capacity)``.

Retention semantics (sustained ingest, paper §3.4: drones offload 60-sample
shards every 5 minutes *indefinitely*): the tuple log is a **ring buffer** —
``tup_count`` counts every tuple ever written and the physical slot is
``position % tuple_capacity``, so once an edge's log is full new tuples
overwrite the oldest ones instead of being dropped. The retained window on an
edge is always the most recent ``min(tup_count, tuple_capacity)`` tuples
(scan validity rule ``slot < min(count, cap)``). ``tup_overwritten`` counts
tuples aged out by retention; ``tup_dropped`` counts tuples actually *lost*
(stays 0 under ring-buffer semantics). Every ``retention_every``-th insert
step derives a per-edge watermark (oldest retained timestamp, once the ring
has wrapped) and runs ``index.retire_entries`` + ``index.compact_index`` so
the shard index tracks the same sliding window instead of saturating.

Query exactness under retention: replicas' rings wrap at independent rates,
and the planner picks one replica per shard without retention awareness, so
exact results are guaranteed for windows that lie inside *every* replica's
retained window (what the sustained-ingest tests and fig15 assert). Windows
straddling the retention boundary are answered best-effort — a
faster-wrapping replica may already have overwritten tuples a slower one
still holds; loss is bounded by the replicas' retention skew.

The per-edge query engine (the paper's InfluxDB role) is a predicate scan —
``repro.kernels.st_scan`` provides the Pallas TPU kernel; ``scan_engine`` here
dispatches to it or to the jnp engine (``st_scan.chunked``), whose OR-list
membership test runs only as many entries as the longest per-edge list.

Phase names: every phase of ``query_local`` (``query.lookup``,
``query.merge``, ``query.plan``, ``query.orlist``, ``query.scan``),
``finalize_query`` (``query.combine``) and ``insert_local``
(``insert.place``, ``insert.ring``, ``insert.retire``, ``insert.index``,
``insert.latest``) runs under a ``jax.named_scope``. A scope only labels the
``op_name`` metadata of the HLO it emits, so a profiler trace attributes
device time to phases; the compiled programs and their answers are those of
the unlabelled code.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import hashing, planner as planner_lib
from repro.core.index import (IndexState, QueryPred, compact_index,
                              init_index, insert_entries, lookup,
                              retire_entries)
from repro.core.placement import ShardMeta, place_replicas
from repro.core.slicing import SliceConfig, spatial_slice_edges, temporal_slice_edges


class EdgeCollectives(NamedTuple):
    """Axis-parameterized collective hook bundle for the shard-local bodies.

    The shard-local bodies (``insert_local`` / ``query_local``) are mesh-
    agnostic: the two metadata-scale cross-device exchanges they need are
    injected through this bundle, so the same bodies serve the single-device
    runtime (identity hooks — ``LOCAL_COLLECTIVES``), the 1-D ``("edge",)``
    mesh, and the 2-D ``("fleet", "edge")`` cross-host mesh
    (``distributed.federation.make_collectives`` builds the bundle from the
    mesh's edge-bearing axes; on the fleet mesh the candidate merge is
    hierarchical — intra-fleet first, inter-fleet over the reduced set).

      gather_watermark: (E_local,) local retention watermark -> (E,) global
          (identity on one device; all-gather over the edge-bearing axes
          under shard_map).
      combine_matched:  (MatchedShards over local edges, max_shards) ->
          globally-merged MatchedShards every device plans against
          (identity on one device; hierarchical all-gather + top-S
          re-dedup under shard_map — bit-identical to the single-device
          lookup, see ``index.dedup_matched``).
    """
    gather_watermark: Callable
    combine_matched: Callable


#: Identity hooks — the 1-device special case (``edge_ids == arange(E)``).
LOCAL_COLLECTIVES = EdgeCollectives(
    gather_watermark=lambda wm: wm,
    combine_matched=lambda matched, max_shards: matched)


def _default_site_grid(n_edges: int) -> Tuple[Tuple[float, float], ...]:
    """Deterministic lat/lon grid over the synthetic-city bbox, slightly
    inset — used when ``sites`` is left empty so a default-constructed
    StoreConfig is immediately usable. Bounds come from CityConfig itself
    (lazy import; the data layer already depends on core) so the default
    deployment region can never drift from the default data region."""
    from repro.data.synthetic import CityConfig
    city = CityConfig()
    pad_lat = 0.08 * (city.lat_max - city.lat_min)
    pad_lon = 0.08 * (city.lon_max - city.lon_min)
    rows = int(np.ceil(np.sqrt(n_edges)))
    cols = int(np.ceil(n_edges / rows))
    lat = np.linspace(city.lat_min + pad_lat, city.lat_max - pad_lat, rows)
    lon = np.linspace(city.lon_min + pad_lon, city.lon_max - pad_lon, cols)
    grid = [(float(la), float(lo)) for la in lat for lo in lon]
    return tuple(grid[:n_edges])


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """Static configuration of an AerialDB deployment."""
    n_edges: int = 20
    sites: Tuple[Tuple[float, float], ...] = ()   # (E, 2) edge locations
    tau: float = 300.0
    slice_cfg: SliceConfig = SliceConfig()
    tuple_capacity: int = 1 << 14                 # ring-buffer slots per edge
    index_capacity: int = 1 << 12                 # index entries per edge
    max_shards_per_query: int = 128               # S
    records_per_shard: int = 60                   # R (paper: 60 samples / 5 min)
    n_values: int = 4                             # sensor channels per tuple
    replication: int = 3                          # 1 => Feather-like baseline
    use_index: bool = True                        # False => broadcast baseline
    planner: str = "min_shards"
    or_group: int = 150                           # paper: sub-queries split at 150 sids
    retention_every: int = 4                      # insert steps between index sweeps
    n_failure_domains: int = 1                    # contiguous device blocks to spread
                                                  # each shard's replicas across
    max_drones: int = 0                           # latest-per-drone hot-cache rows
                                                  # (0 disables the cache)

    def __post_init__(self):
        if not (1 <= self.replication <= 3):
            raise ValueError(
                f"replication={self.replication} is unsupported: index entries "
                "carry exactly 3 replica slots (paper §3.4.2); pass "
                "1 <= replication <= 3.")
        if not self.use_index and self.replication != 1:
            raise ValueError(
                f"use_index=False with replication={self.replication} would "
                f"overcount results ~{self.replication}x: the broadcast "
                "baseline has no shard scoping, so every replica edge scans "
                "every tuple. Use replication=1 for the Feather-like "
                "baseline, or keep the index enabled.")
        if self.retention_every < 1:
            raise ValueError(
                f"retention_every={self.retention_every} must be >= 1 (index "
                "retention sweeps run every retention_every insert steps).")
        if self.max_drones < 0:
            raise ValueError(
                f"max_drones={self.max_drones} must be >= 0: it sizes the "
                "latest-per-drone hot cache (0 disables it; drone ids >= "
                "max_drones are not cached).")
        if self.n_failure_domains < 1 or self.n_edges % self.n_failure_domains:
            raise ValueError(
                f"n_failure_domains={self.n_failure_domains} must be >= 1 and "
                f"divide n_edges={self.n_edges}: failure domains are the "
                "contiguous device blocks of the sharded layout contract "
                "(one block of E / n_failure_domains edges each).")
        if not self.sites:
            object.__setattr__(self, "sites", _default_site_grid(self.n_edges))
        elif len(self.sites) != self.n_edges:
            raise ValueError(
                f"sites has {len(self.sites)} entries but n_edges="
                f"{self.n_edges}; pass one (lat, lon) per edge or leave "
                "sites=() for a deterministic default grid.")

    @property
    def tuple_width(self) -> int:
        return 3 + self.n_values

    @property
    def padded_capacity(self) -> int:
        """Stored (lane-aligned) size of the tuple axis: ``tuple_capacity``
        rounded up to a 128 multiple, so the column-major log's minor dim is
        always vector-lane aligned. Slots >= ``tuple_capacity`` are dead —
        never written, never scanned."""
        return -(-self.tuple_capacity // 128) * 128

    def sites_array(self) -> jnp.ndarray:
        return jnp.asarray(np.asarray(self.sites, np.float32).reshape(self.n_edges, 2))


class StoreState(NamedTuple):
    index: IndexState
    tup_f: jnp.ndarray
    tup_sid: jnp.ndarray
    tup_count: jnp.ndarray        # (E,) total tuples ever written (monotonic;
                                  #      saturates near 2^31 — see _COUNT_SAT)
    tup_pos: jnp.ndarray          # (E,) ring write cursor, always in [0, cap)
    tup_overwritten: jnp.ndarray  # (E,) tuples aged out by ring retention
    tup_dropped: jnp.ndarray      # (E,) tuples actually lost (0 by design)
    steps: jnp.ndarray            # () insert steps executed (retention cadence)
    latest_f: jnp.ndarray         # (D, 3+V) latest-per-drone hot cache —
                                  #      max-t record per drone id, REPLICATED
                                  #      across the mesh (D = cfg.max_drones)
    latest_seen: jnp.ndarray      # (D,) insert step that last updated each
                                  #      drone's cache row; -1 = never seen


class LatestResult(NamedTuple):
    """``AerialDB.latest()`` / ``Query().latest()`` answer: the O(drones)
    hot-cache read (paper §4.4 near-real-time shape — Wingxtra's "latest
    position matters more than history" rule), bypassing the log scan and
    the index entirely.

      record:    (D, 3+V) last (max-t) record per drone id; rows of drones
                 never seen are zeros. Channels a partial payload never
                 filled are NaN (the validity mask is ``isfinite``).
      last_seen: (D,) insert step that wrote each row (-1 = never seen).
      valid:     (D,) ``last_seen >= 0``.

    Staleness bound: the cache never forgets — each row is the max-t record
    ever *inserted* for that drone, even after ring retention has aged the
    tuple itself out of the log, and is exact the moment the insert that
    carried it completes (no scan, no index lookup, no planner).
    """
    record: jnp.ndarray
    last_seen: jnp.ndarray
    valid: jnp.ndarray


# The monotonic counter saturates here instead of wrapping int32 negative
# (which would silently blank every scan). The ring write position uses
# tup_pos, which never overflows, so ingest continues correctly past this
# point — only the total-written telemetry stops being exact.
_COUNT_SAT = (1 << 31) - (1 << 26)


AGG_OPS = ("count", "sum", "min", "max", "mean")


@dataclasses.dataclass(frozen=True, init=False)
class AggSpec:
    """Static aggregation spec: which sensor channel(s) to aggregate and
    which aggregates the caller asked for (paper §4.5's range-*aggregation*
    workloads over arbitrary channels).

    The spec is static (hashable — a jit static argument / shard_map cache
    key): ``channels`` selects the value rows ``3 + channel`` of the
    column-major log all the way down into both scan engines, which evaluate
    the predicate mask ONCE and accumulate every requested channel's fused
    (count, sum, min, max) set in the same single pass over the log — a
    K-channel spec costs one scan, not K (the marginal accumulators are nil
    next to the predicate evaluation). ``mean`` is derived after the final
    (Q, E) combine (``finalize_query``), which keeps sum/count the only
    cross-device reductions. ``ops`` records the caller's projection; apply
    it with ``QueryResult.view``. Only ``channels`` is a compile-time cache
    key — specs differing in ``ops`` alone share one compiled scan.

    Construct with either ``channel=`` (one channel, the common case) or
    ``channels=`` (a static tuple batched into one scan); a single-channel
    spec produces (Q,)-shaped aggregates, a multi-channel spec (Q, K).
    """
    channels: Tuple[int, ...] = (0,)
    ops: Tuple[str, ...] = AGG_OPS

    def __init__(self, channel: Optional[int] = None,
                 ops: Tuple[str, ...] = AGG_OPS,
                 channels: Optional[Tuple[int, ...]] = None):
        if channel is not None and channels is not None:
            raise ValueError(
                "pass channel= (single) OR channels= (batched), not both.")
        if channels is None:
            channels = (0 if channel is None else channel,)
        if isinstance(channels, int):
            channels = (channels,)
        channels = tuple(int(c) for c in channels)
        ops = (ops,) if isinstance(ops, str) else tuple(ops)
        object.__setattr__(self, "channels", channels)
        object.__setattr__(self, "ops", ops)
        unknown = [op for op in self.ops if op not in AGG_OPS]
        if unknown:
            raise ValueError(
                f"unknown aggregate op(s) {unknown}: pick from {AGG_OPS}.")
        if not self.ops:
            raise ValueError("AggSpec.ops is empty: request at least one of "
                             f"{AGG_OPS}.")
        if not self.channels:
            raise ValueError("AggSpec.channels is empty: select at least one "
                             "sensor channel.")
        if len(set(self.channels)) != len(self.channels):
            raise ValueError(
                f"channels={self.channels} contains duplicates: each channel "
                "is aggregated once per scan; deduplicate the request.")
        for c in self.channels:
            if c < 0:
                raise ValueError(f"channel={c} must be >= 0.")

    @property
    def channel(self) -> int:
        """First (for single-channel specs: the only) selected channel."""
        return self.channels[0]

    @property
    def n_channels(self) -> int:
        return len(self.channels)

    def validate_for(self, cfg: "StoreConfig") -> "AggSpec":
        for c in self.channels:
            if c >= cfg.n_values:
                raise ValueError(
                    f"channel={c} out of range: this deployment stores "
                    f"n_values={cfg.n_values} sensor channels per tuple "
                    f"(valid channels 0..{cfg.n_values - 1}).")
        return self


class QueryResult(NamedTuple):
    """Fixed-shape query answer: aggregates over matching tuples of the
    ``AggSpec``-selected sensor channel(s).

    Value aggregates are (Q,) float32 for a single-channel spec and (Q, K)
    for a K-channel spec (one column per requested channel, in spec order);
    ``count`` is channel-independent and always (Q,). All value aggregates
    (min/max/mean) are NaN for queries that matched nothing."""
    count: jnp.ndarray    # (Q,) int32
    vsum: jnp.ndarray     # (Q[, K]) float32 — sum of the selected channel(s)
    vmin: jnp.ndarray     # (Q[, K]) float32 (NaN when count==0)
    vmax: jnp.ndarray     # (Q[, K]) float32 (NaN when count==0)
    overflow: jnp.ndarray # (Q,) bool — matched shards exceeded the static budget
    vmean: jnp.ndarray = None  # (Q[, K]) float32 — vsum/count (NaN when count==0)
    completeness_bound: jnp.ndarray = None  # (Q,) float32 — see QueryInfo
    replicas_lost: jnp.ndarray = None       # (Q,) int32 — see QueryInfo

    def view(self, agg: AggSpec) -> dict:
        """Project the aggregates the spec asked for plus the degradation
        telemetry every caller should see: op name -> array — ``count`` is
        (Q,); value ops are (Q,) for a single-channel spec and (Q, K) for a
        K-channel spec (one column per channel, spec order).

        The view always carries ``completeness_bound`` (planner-assigned
        fraction of the index-visible shard set; 1.0 when fully served, NaN
        when unknown — overflow or broadcast) and ``replicas_lost`` (dead
        replica slots over the matched shards) so applications observe
        degraded answers without digging through ``QueryInfo``. See the
        ``QueryInfo`` docstring for the bound's exact (shard-weighted,
        index-visible) semantics and caveat."""
        full = {"count": self.count, "sum": self.vsum, "min": self.vmin,
                "max": self.vmax, "mean": self.vmean}
        out = {op: full[op] for op in agg.ops}
        out["completeness_bound"] = self.completeness_bound
        out["replicas_lost"] = self.replicas_lost
        return out


class QueryInfo(NamedTuple):
    """Telemetry used by the paper-figure benchmarks (Fig 9–14).

    Degraded-query accounting (paper §4.5.3 resilience): ``replicas_lost``
    counts dead replica slots over the matched shard set, and
    ``completeness_bound`` is ``assigned_shards / matched_shards`` — the
    planner-assigned fraction of the *index-visible* shard set (1.0 when
    every matched shard has a live replica; shards whose entire replica set
    is dead are unassignable and pull it below 1). It is NOT a tuple-level
    floor in general: the fraction is shard-weighted, and a shard whose
    every index entry died with its edges never appears in ``matched`` at
    all — so without failure-domain spreading it can sit ABOVE the true
    tuple completeness (fig14's spread=0 row demonstrates exactly that).
    Under failure-domain spreading with <= replication-1 edge failures (or
    one whole device), entry over-replication keeps every shard visible and
    assignable, and the value is exactly 1.0 — which is what the fig14 CI
    gate asserts. When ``overflow`` clipped the match, or on the index-free
    broadcast baseline (``shards_matched == -1``), it is NaN (unknown)
    rather than a fabricated 1.0."""
    lookup_edges: jnp.ndarray      # (Q,) #edges consulted for the index lookup
    subquery_edges: jnp.ndarray    # (Q,) #edges executing sub-queries
    shards_matched: jnp.ndarray    # (Q,) #distinct shards
    max_shards_per_edge: jnp.ndarray  # (Q,) worst per-edge OR-list length
    broadcast: jnp.ndarray         # (Q,) bool — index lookup degenerated
    replicas_lost: jnp.ndarray     # (Q,) dead replica slots over matched shards
    completeness_bound: jnp.ndarray  # (Q,) float32 assigned/matched (NaN unknown)


def _concrete(x, q):
    if isinstance(x, jax.core.Tracer):
        return None
    try:
        return np.broadcast_to(np.asarray(x), (q,))
    except Exception:
        return None


def _check_ranges(q, pairs, enabled, is_and):
    """Reject inverted ranges on concrete (non-traced) inputs: under an AND
    predicate an inverted bound makes the whole query match nothing, which
    historically returned silently-empty results. OR predicates are exempt —
    there an inverted clause merely contributes nothing while the other
    clauses still match. Tracers skip the check."""
    en, am = _concrete(enabled, q), _concrete(is_and, q)
    if en is None or am is None:
        return
    en = en & am
    if not en.any():
        return
    for name, lo, hi in pairs:
        lo, hi = _concrete(lo, q), _concrete(hi, q)
        if lo is None or hi is None:
            continue
        bad = en & (np.asarray(lo) > np.asarray(hi))
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"inverted {name} range for query {i}: "
                f"{name}0={float(lo[i])} > {name}1={float(hi[i])}. Inverted "
                "ranges match nothing under an AND predicate; swap the "
                "bounds (ranges are inclusive [lo, hi]).")


def make_pred(q: int = 1, lat0=0.0, lat1=0.0, lon0=0.0, lon1=0.0, t0=0.0,
              t1=0.0, sid_hi=-1, sid_lo=-1, has_spatial=False,
              has_temporal=False, has_sid=False, is_and=True) -> QueryPred:
    """Build a batched QueryPred, broadcasting scalars to (q,).

    Inverted ranges (``lat1 < lat0``, ``lon1 < lon0``, ``t1 < t0``) on
    concrete inputs under an AND predicate raise — they would silently match
    nothing. The ``repro.api.Query`` builder performs the same validation
    eagerly (for every clause, since the builder composes clause-wise).
    """
    def arr(x, dt):
        a = jnp.asarray(x, dt)
        return jnp.broadcast_to(a, (q,) if a.ndim == 0 else a.shape)

    with TraceAnnotation("aerialdb.make_pred"):
        _check_ranges(q, [("lat", lat0, lat1), ("lon", lon0, lon1)],
                      has_spatial, is_and)
        _check_ranges(q, [("t", t0, t1)], has_temporal, is_and)
        return QueryPred(
            lat0=arr(lat0, jnp.float32), lat1=arr(lat1, jnp.float32),
            lon0=arr(lon0, jnp.float32), lon1=arr(lon1, jnp.float32),
            t0=arr(t0, jnp.float32), t1=arr(t1, jnp.float32),
            sid_hi=arr(sid_hi, jnp.int32), sid_lo=arr(sid_lo, jnp.int32),
            has_spatial=arr(has_spatial, jnp.bool_),
            has_temporal=arr(has_temporal, jnp.bool_),
            has_sid=arr(has_sid, jnp.bool_), is_and=arr(is_and, jnp.bool_))


def init_store(cfg: StoreConfig) -> StoreState:
    e = cfg.n_edges
    return StoreState(
        index=init_index(e, cfg.index_capacity),
        tup_f=jnp.zeros((e, cfg.tuple_width, cfg.padded_capacity), jnp.float32),
        tup_sid=jnp.full((e, 2, cfg.padded_capacity), -1, jnp.int32),
        tup_count=jnp.zeros((e,), jnp.int32),
        tup_pos=jnp.zeros((e,), jnp.int32),
        tup_overwritten=jnp.zeros((e,), jnp.int32),
        tup_dropped=jnp.zeros((e,), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        latest_f=jnp.zeros((cfg.max_drones, cfg.tuple_width), jnp.float32),
        latest_seen=jnp.full((cfg.max_drones,), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Insertion (paper §3.4, Fig 2)
# ---------------------------------------------------------------------------

def _index_edge_mask(cfg: StoreConfig, meta: ShardMeta, replicas: jnp.ndarray,
                     sites: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """(B, E) — edges that must hold this shard's index entry: every spatial
    and temporal slice owner, plus the replica edges themselves (§3.4.3).
    Ranges wider than the static slice budget broadcast their entry (the
    entry is tiny; the paper notes wide shards index 'on many more edges')."""
    e = cfg.n_edges
    sm, s_ovf = spatial_slice_edges(meta.lat0, meta.lat1, meta.lon0, meta.lon1,
                                    sites, cfg.slice_cfg)
    tm, t_ovf = temporal_slice_edges(meta.t0, meta.t1, e, cfg.slice_cfg)
    rep_mask = jnp.any(replicas[..., None] == jnp.arange(e, dtype=jnp.int32), axis=1)
    mask = sm | tm | rep_mask
    mask = jnp.where((s_ovf | t_ovf)[:, None], jnp.ones_like(mask), mask)
    return mask & alive[None, :]


def _update_latest(latest_f: jnp.ndarray, latest_seen: jnp.ndarray,
                   payload: jnp.ndarray, sid_hi: jnp.ndarray,
                   steps: jnp.ndarray):
    """Latest-per-drone hot-cache update (the §4.4 near-real-time fast path).

    Deterministic under duplicate drone ids: ``.at[].set`` with duplicate
    scatter indices has unspecified winner order in XLA, so the per-drone
    argmax is built from two COMMUTATIVE ``.at[].max`` scatters instead —
    (1) max-t per drone, (2) max flat index among the records achieving that
    t (so t ties resolve to the last record in the batch, matching the host
    oracle's "latest arrival wins" rule). Records with non-finite t are
    excluded; drone ids outside [0, D) fall off via mode="drop".

    Inputs are replicated under shard_map (payload/meta/steps plus the
    previous replicated cache), so every device computes the identical new
    cache and the P() out-spec is sound without a collective.
    """
    d = latest_f.shape[0]
    b, r, w = payload.shape
    flat = payload.reshape(b * r, w)                              # (N, W)
    did = jnp.broadcast_to(sid_hi[:, None], (b, r)).reshape(-1)   # (N,)
    t = flat[:, 0]
    # Negative ids would WRAP under .at[] scatter semantics (mode="drop" only
    # guards the high side) — neutralise them alongside non-finite t.
    vmask = jnp.isfinite(t) & (did >= 0)
    t_clean = jnp.where(vmask, t, -jnp.inf)
    cand_t = jnp.full((d,), -jnp.inf, jnp.float32).at[did].max(
        t_clean, mode="drop")                                     # (D,)
    hit = vmask & (t_clean == jnp.take(cand_t, did, mode="fill",
                                       fill_value=jnp.inf))
    idx = jnp.where(hit, jnp.arange(b * r, dtype=jnp.int32), -1)
    best = jnp.full((d,), -1, jnp.int32).at[did].max(idx, mode="drop")
    cur_t = jnp.where(latest_seen >= 0, latest_f[:, 0], -jnp.inf)
    newer = (best >= 0) & (cand_t >= cur_t)
    latest_f = jnp.where(newer[:, None],
                         jnp.take(flat, jnp.maximum(best, 0), axis=0),
                         latest_f)
    latest_seen = jnp.where(newer, steps, latest_seen)
    return latest_f, latest_seen


def insert_local(cfg: StoreConfig, state: StoreState, payload: jnp.ndarray,
                 meta: ShardMeta, alive: jnp.ndarray, edge_ids: jnp.ndarray,
                 collectives: EdgeCollectives = LOCAL_COLLECTIVES):
    """Shard-local insert body — placement, replication, indexing.

    ``state`` arrays carry a slice of the logical edge axis whose global ids
    are ``edge_ids`` (the full ``arange(E)`` on one device); ``payload``,
    ``meta``, ``alive`` are global and replicated. Placement and slice masks
    are metadata-scale, recomputed replicated on every shard; the tuple
    scatter and index writes touch only the local edges.

    ``collectives.gather_watermark`` maps this shard's (E_local,) retention
    watermark to the global (E,) watermark that ``retire_entries`` needs
    (entries name replica edges anywhere in the deployment): identity on one
    device, an all-gather over the mesh's edge-bearing axes under shard_map.

    Returns (new_state, info dict) with per-edge info sliced like ``state``.
    """
    cap = cfg.tuple_capacity
    e_loc = edge_ids.shape[0]
    b, r, w = payload.shape
    sites = cfg.sites_array()

    with jax.named_scope("insert.place"):
        replicas = place_replicas(meta, sites, alive, cfg.tau,
                                  n_domains=cfg.n_failure_domains)  # (B, 3)
        replicas = replicas[:, : cfg.replication]
        alive_loc = jnp.take(alive, edge_ids)
        # --- tuple dispatch: one-hot shard->edge routing (MoE-style) ---
        dm = jnp.any(replicas[..., None] == edge_ids, axis=1)    # (B, E_loc)
        dm = dm & alive_loc[None, :]

    with jax.named_scope("insert.ring"):
        rank = jnp.cumsum(dm, axis=0) - 1                        # (B, E_loc)
        start = state.tup_pos[None, :] + rank * r                # (B, E_loc)
        pos = start[..., None] + jnp.arange(r, dtype=jnp.int32)  # (B,E_loc,R)
        ok = dm[..., None]
        # Ring slot modulo the LOGICAL capacity (lane-padding slots stay
        # dead); the drop sentinel must be out of range of the PADDED tuple
        # axis.
        pp = jnp.where(ok, pos % cap, cfg.padded_capacity)
        ee = jnp.broadcast_to(
            jnp.arange(e_loc, dtype=jnp.int32)[None, :, None], (b, e_loc, r))

        pay = jnp.broadcast_to(payload[:, None], (b, e_loc, r, w))
        sid = jnp.broadcast_to(
            jnp.stack([meta.sid_hi, meta.sid_lo], axis=-1)[:, None, None, :],
            (b, e_loc, r, 2))

        # Column-major write pattern: one scatter per tuple writes its whole
        # field COLUMN tup_f[e, :, slot] (the slice between the advanced
        # indices spans the field rows), so the lane-aligned log never needs
        # a query-time relayout.
        tup_f = state.tup_f.at[ee, :, pp].set(pay, mode="drop")
        tup_sid = state.tup_sid.at[ee, :, pp].set(sid, mode="drop")
        n_in = jnp.sum(dm, axis=0) * r                           # (E_loc,)
        tup_pos = ((state.tup_pos + n_in) % cap).astype(jnp.int32)
        tup_count = jnp.minimum(state.tup_count + n_in,
                                _COUNT_SAT).astype(jnp.int32)    # monotonic
        # Retention telemetry: slots reclaimed from the previous window.
        valid_before = jnp.minimum(state.tup_count, cap)
        valid_after = jnp.minimum(tup_count, cap)
        overwritten_now = (valid_before + n_in - valid_after).astype(jnp.int32)
        tup_overwritten = jnp.minimum(state.tup_overwritten + overwritten_now,
                                      _COUNT_SAT).astype(jnp.int32)

    # --- index retention (cadenced): retire entries whose data has aged out
    # of every replica edge's ring, then compact so the cursor is reusable.
    # Runs BEFORE this batch's index writes so freed slots host the fresh
    # entries. Watermarks (oldest retained timestamp; -inf until the edge
    # has ever aged out a tuple — wrap OR repair-time ring reclamation, i.e.
    # tup_overwritten > 0, so retention resumes after a reclaimed ring is
    # rewound below cap) are only computed on sweep steps — the (E, CAP)
    # reduction stays off the ingest hot path. The watermark gather sits
    # OUTSIDE the cond so
    # every device executes the same collective schedule regardless of how
    # rep-checking handles conditional branches. ---
    def _local_wm(_):
        retained = (jnp.arange(cfg.padded_capacity, dtype=jnp.int32)[None, :]
                    < valid_after[:, None])                      # (E_loc, CAP_L)
        t_oldest = jnp.min(jnp.where(retained, tup_f[:, 0, :], jnp.inf),
                           axis=1)                               # t row
        # Epoch-aware: after repair's ring reclamation rewinds tup_count
        # below cap, tup_overwritten > 0 still marks the edge as having
        # lost tuples — without it the watermark would read -inf and
        # retention would silently pause until the ring re-wrapped.
        lossy = (tup_count > cap) | (tup_overwritten > 0)
        return jnp.where(lossy, t_oldest,
                         -jnp.inf).astype(jnp.float32)           # (E_loc,)

    with jax.named_scope("insert.retire"):
        steps = state.steps + 1
        do_sweep = steps % cfg.retention_every == 0
        wm_local = jax.lax.cond(
            do_sweep, _local_wm,
            lambda _: jnp.full((e_loc,), -jnp.inf, jnp.float32), None)
        watermark = collectives.gather_watermark(wm_local)       # (E,) global
        index = jax.lax.cond(
            do_sweep, lambda ix: compact_index(retire_entries(ix, watermark)),
            lambda ix: ix, state.index)

    # --- sliced index entries (§3.4.3) ---
    with jax.named_scope("insert.index"):
        idx_mask = _index_edge_mask(cfg, meta, replicas, sites,
                                    alive)                       # (B, E)
        idx_mask = jnp.take(idx_mask, edge_ids, axis=1)          # (B, E_loc)
        index = insert_entries(
            index, meta,
            jnp.pad(replicas, ((0, 0), (0, 3 - cfg.replication)),
                    constant_values=-1),
            idx_mask, step=steps)

    # --- latest-per-drone hot cache: replicated O(D) state, updated on the
    # ingest path from the same replicated payload (statically compiled out
    # when the cache is disabled so existing graphs are untouched). ---
    latest_f, latest_seen = state.latest_f, state.latest_seen
    if cfg.max_drones:
        with jax.named_scope("insert.latest"):
            latest_f, latest_seen = _update_latest(
                latest_f, latest_seen, payload, meta.sid_hi, steps)

    new_state = StoreState(index, tup_f, tup_sid, tup_count, tup_pos,
                           tup_overwritten, state.tup_dropped, steps,
                           latest_f, latest_seen)
    info = {
        "replicas": replicas,
        "intake_per_edge": n_in,
        "index_writes_per_edge": jnp.sum(idx_mask, axis=0),
        "tuples_overwritten": overwritten_now,
        "tuples_dropped": jnp.zeros_like(n_in),
        # Ingest-time index-capacity drops (per-edge delta this step): the
        # session ledger folds the batch's sids into the incremental-repair
        # pending set whenever this is nonzero, closing the repair() vs
        # repair(full=True) gap for drops outside swept shards.
        "index_entries_dropped": index.dropped - state.index.dropped,
        "index_entries_retired": index.retired - state.index.retired,
        "retention_watermark": watermark,
    }
    return new_state, info


def check_batch_fits(cfg: StoreConfig, payload_shape) -> None:
    """Reject batches that could wrap one edge's ring within a single insert
    (scatter order would be undefined). Static — call before tracing."""
    b, r = payload_shape[0], payload_shape[1]
    if b * r > cfg.tuple_capacity:
        raise ValueError(
            f"batch writes {b}x{r}={b * r} tuples, exceeding tuple_capacity="
            f"{cfg.tuple_capacity}: one edge could wrap its own ring within a "
            "single insert_step (scatter order would be undefined). Split the "
            "batch or raise tuple_capacity.")


@partial(jax.jit, static_argnums=(0,))
def _insert_step_jit(cfg: StoreConfig, state: StoreState, payload: jnp.ndarray,
                     meta: ShardMeta, alive: jnp.ndarray):
    edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
    return insert_local(cfg, state, payload, meta, alive, edge_ids)


def _insert(cfg: StoreConfig, state: StoreState, payload: jnp.ndarray,
            meta: ShardMeta, alive: jnp.ndarray):
    """1-device insert body shared by the ``AerialDB`` facade and the
    deprecated ``insert_step`` shim: batch-fit check + jitted insert_local."""
    check_batch_fits(cfg, payload.shape)
    return _insert_step_jit(cfg, state, payload, meta, alive)


@lru_cache(maxsize=None)
def _warn_deprecated(old: str, new: str):
    """One DeprecationWarning per (old, new) pair per process — the step
    shims sit on hot loops in older callers."""
    warnings.warn(
        f"{old} is deprecated: drive the store through {new} (the unified "
        "repro.api facade owns state/alive/key plumbing and dispatches to "
        "the single-device or federated runtime from one entry point). The "
        "shim remains supported and bit-identical.",
        DeprecationWarning, stacklevel=3)


def insert_step(cfg: StoreConfig, state: StoreState, payload: jnp.ndarray,
                meta: ShardMeta, alive: jnp.ndarray):
    """Insert B shards (R tuples each) — the 1-device special case of
    ``insert_local`` (see the sharded-state layout contract in the module
    docstring; ``repro.distributed.federation`` runs the same body over a
    device mesh).

    .. deprecated:: kept as a thin shim over the same body the
       ``repro.api.AerialDB`` facade drives; prefer ``AerialDB.insert``.

    The tuple log is a ring buffer: writes land at ``position % capacity``
    (oldest-first overwrite), so inserts never saturate; every
    ``cfg.retention_every``-th call additionally retires + compacts index
    entries that aged out of the retained window.

    Args:
      payload: (B, R, 3+V) tuple records (t, lat, lon, values...).
      meta:    ShardMeta of the B shards.
      alive:   (E,) availability mask.

    Returns (new_state, info dict).
    """
    _warn_deprecated("insert_step", "repro.api.AerialDB.insert")
    return _insert(cfg, state, payload, meta, alive)


# ---------------------------------------------------------------------------
# Query (paper §3.5, Fig 4)
# ---------------------------------------------------------------------------

def _lookup_sets(cfg: StoreConfig, pred: QueryPred, sites: jnp.ndarray,
                 alive: jnp.ndarray):
    """Candidate edge sets E_s, E_t, E_i for the index lookup (§3.5.1) and
    the chosen lookup mask. AND => smallest failure-free set; OR => union.
    Any unusable situation falls back to broadcasting to alive edges."""
    e = cfg.n_edges
    q = pred.lat0.shape[0]

    es, s_ovf = spatial_slice_edges(pred.lat0, pred.lat1, pred.lon0, pred.lon1,
                                    sites, cfg.slice_cfg)
    et, t_ovf = temporal_slice_edges(pred.t0, pred.t1, e, cfg.slice_cfg)
    ei = (hashing.hash_shard_id(pred.sid_hi, pred.sid_lo, e)[..., None]
          == jnp.arange(e, dtype=jnp.int32))

    sets = jnp.stack([es, et, ei], axis=1)                       # (Q, 3, E)
    usable = jnp.stack([pred.has_spatial & ~s_ovf,
                        pred.has_temporal & ~t_ovf,
                        pred.has_sid], axis=1)                   # (Q, 3)
    has_failed = jnp.any(sets & ~alive, axis=-1)                 # (Q, 3)
    sizes = jnp.sum(sets, axis=-1)                               # (Q, 3)

    # §3.5.3: prefer failure-free sets; among them the smallest.
    big = jnp.int32(1 << 30)
    score = jnp.where(usable & ~has_failed, sizes, big)
    best = jnp.argmin(score, axis=-1)                            # (Q,)
    best_ok = jnp.take_along_axis(score, best[:, None], axis=1)[:, 0] < big

    chosen = jnp.take_along_axis(sets, best[:, None, None], axis=1)[:, 0]  # (Q, E)
    union = jnp.any(jnp.where(usable[..., None], sets, False), axis=1)
    union_ok = jnp.any(usable, axis=-1) & ~jnp.any(union & ~alive, axis=-1)

    is_and = pred.is_and
    mask = jnp.where(is_and[:, None], chosen, union)
    ok = jnp.where(is_and, best_ok, union_ok)
    if not cfg.use_index:
        ok = jnp.zeros_like(ok)                                  # Feather-like: no index
    broadcast = ~ok
    mask = jnp.where(broadcast[:, None], jnp.broadcast_to(alive, (q, e)), mask & alive)
    return mask, broadcast


def scan_engine(tup_f, tup_sid, tup_count, pred: QueryPred, sublists,
                sublist_len, use_kernel: bool = False,
                interpret: Optional[bool] = None,
                channels: Tuple[int, ...] = (0,),
                valid_c: Optional[int] = None):
    """Per-edge predicate scan (the InfluxDB role). Evaluates each query's
    predicate + shard OR-list against the edge-local retained window
    (``slot < min(tup_count, valid_c)`` — ring-buffer validity over the
    logical capacity; the stored tuple axis may be lane-padded above it).

    Single pass: the whole query batch and every requested channel are
    answered in ONE sweep over the column-major log — the Pallas kernel
    tiles queries so each resident tuple tile serves a ``block_q``-query
    tile, and both engines fuse all K channels' aggregates behind one
    predicate mask.

    Args:
      tup_f/tup_sid: column-major (E, 3+V, C) / (E, 2, C) — the native
                   StoreState layout, streamed as-is (no relayout).
      sublists:    (Q, E, L, 2) int32 shard ids assigned to each (query, edge).
      sublist_len: (Q, E) int32 — #valid entries in each OR-list.
      use_kernel:  dispatch to the Pallas TPU kernel instead of the jnp
                   engine (``st_scan_chunked``: the oracle's results, its
                   OR-list test bounded by the longest per-edge list).
      interpret:   force Pallas interpret mode; None = auto (compiled on TPU,
                   interpreted elsewhere).
      channels:    static tuple of sensor channels to aggregate
                   (``AggSpec.channels``); value rows ``3 + channel``.
      valid_c:     logical ring capacity (``StoreConfig.tuple_capacity``);
                   None = the stored C (unpadded input).

    Returns (count, vsum, vmin, vmax): count (Q, E) int32; vsum/vmin/vmax
    (Q, K, E) float32 per-channel partials.
    """
    if use_kernel:
        from repro.kernels.st_scan import ops as st_ops
        return st_ops.st_scan(tup_f, tup_sid, tup_count, pred, sublists,
                              sublist_len, interpret=interpret,
                              channels=channels, valid_c=valid_c)
    from repro.kernels.st_scan.chunked import st_scan_chunked
    return st_scan_chunked(tup_f, tup_sid, tup_count, pred, sublists,
                           sublist_len, channels=channels, valid_c=valid_c)


def _tile_slices(q: int, n_tiles: int):
    """Split the static query-batch dim into ``min(n_tiles, q)`` contiguous
    slices, as evenly as possible (sizes differ by at most 1)."""
    n = max(1, min(n_tiles, q))
    base, rem = divmod(q, n)
    out, start = [], 0
    for i in range(n):
        size = base + (1 if i < rem else 0)
        out.append(slice(start, start + size))
        start += size
    return out


def query_local(cfg: StoreConfig, state: StoreState, pred: QueryPred,
                alive: jnp.ndarray, key: jax.Array, edge_ids: jnp.ndarray,
                collectives: EdgeCollectives = LOCAL_COLLECTIVES,
                use_kernel: bool = False, interpret: Optional[bool] = None,
                agg: AggSpec = AggSpec(), overlap_tiles: int = 1):
    """Shard-local query body: index lookup -> candidate merge -> planning ->
    per-edge sub-query scan, over the slice of the edge axis named by
    ``edge_ids``.

    Lookup-set selection and planning are metadata-scale and computed
    replicated from the global ``pred``/``alive``; the index match and the
    tuple scan touch only local state. ``collectives.combine_matched`` merges
    per-shard candidate lists into the global ``MatchedShards`` every device
    plans against: identity on one device; under shard_map, a (hierarchical)
    all-gather of each device's local top-S candidates re-deduplicated with
    ``index.dedup_matched`` (exactly the single-device result — see there).

    Collective/compute overlap: with ``overlap_tiles > 1`` the query batch is
    split into that many tiles and every tile's index match + candidate merge
    is issued BEFORE any tile's log scan — the merge collectives of tile t+1
    (on the fleet mesh: the cross-host inter-fleet exchange) carry no data
    dependency on tile t's scan, so the latency-hiding scheduler can overlap
    them (double-buffered at the default ``overlap_tiles=2`` the federated
    runtime uses on multi-fleet meshes). Every per-query computation here —
    lookup, dedup, planning (per-query folded PRNG keys), OR-list build, scan
    — is query-independent, so results are bitwise invariant to the tiling;
    the differential harness pins that.

    Returns (partials, sublist_len, (lookup_mask, broadcast, overflow,
    shards_matched, replicas_lost, completeness_bound)): ``partials`` are the
    per-edge aggregates — (Q, E_local)
    count plus (Q, K, E_local) per-channel value aggregates for the
    ``agg.channels`` tuple, all produced by ONE scan of the local log;
    ``sublist_len`` is (Q, E_local); the rest is replicated metadata. Feed
    the pieces (with per-edge arrays concatenated back to full E) to
    ``finalize_query`` for the final combine.
    """
    q = pred.lat0.shape[0]
    s = cfg.max_shards_per_query
    e_loc = edge_ids.shape[0]
    sites = cfg.sites_array()

    with jax.named_scope("query.lookup"):
        lookup_mask, broadcast = _lookup_sets(cfg, pred, sites,
                                              alive)             # (Q, E)
        lookup_loc = jnp.take(lookup_mask, edge_ids, axis=1)     # (Q, E_loc)

    if not cfg.use_index:
        # Broadcast baseline (Feather-like): no shard scoping; every alive
        # edge scans everything. StoreConfig rejects use_index=False with
        # replication > 1, which would overcount ~R-fold here. No candidate
        # merge means nothing to overlap — the batch stays untiled.
        with jax.named_scope("query.orlist"):
            alive_loc = jnp.take(alive, edge_ids)
            sublists = jnp.zeros((q, e_loc, 1, 2), jnp.int32)
            sublist_len = jnp.where(jnp.broadcast_to(alive_loc, (q, e_loc)),
                                    -1, 0).astype(jnp.int32)
        ovf = jnp.zeros((q,), jnp.bool_)
        shards_matched = jnp.full((q,), -1, jnp.int32)
        # No index: no shard tracking, so completeness is unknowable here.
        replicas_lost = jnp.zeros((q,), jnp.int32)
        bound = jnp.full((q,), jnp.nan, jnp.float32)
        with jax.named_scope("query.scan"):
            partials = scan_engine(state.tup_f, state.tup_sid,
                                   state.tup_count, pred, sublists,
                                   sublist_len, use_kernel, interpret,
                                   channels=agg.channels,
                                   valid_c=cfg.tuple_capacity)
        return partials, sublist_len, (lookup_mask, broadcast, ovf,
                                       shards_matched, replicas_lost, bound)

    # Per-query planner keys (key folded with the GLOBAL query index), so
    # planner randomness is invariant to the tiling below.
    with jax.named_scope("query.plan"):
        qkeys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(q))

    # Phase 1 — index match + candidate merge for EVERY tile up front: all
    # cross-device exchanges are issued before any log scan.
    tiles = _tile_slices(q, overlap_tiles)
    pred_tiles = [jax.tree.map(lambda a: a[sl], pred) for sl in tiles]
    matched_tiles = []
    for sl, p in zip(tiles, pred_tiles):
        with jax.named_scope("query.lookup"):
            local = lookup(state.index, p, lookup_loc[sl], s)
        with jax.named_scope("query.merge"):
            matched_tiles.append(collectives.combine_matched(local, s))

    # Phase 2 — plan + per-edge OR-lists + single-pass scan, per tile (tile
    # t's scan is dependency-free of tile t+1's in-flight merge).
    outs = []
    for sl, p, matched in zip(tiles, pred_tiles, matched_tiles):
        qt = p.lat0.shape[0]
        with jax.named_scope("query.plan"):
            assignment = planner_lib.plan(cfg.planner, matched, alive,
                                          qkeys[sl])              # (Qt, S)
        with jax.named_scope("query.orlist"):
            # Per-edge OR-lists: rank of shard within its assigned edge.
            am = (assignment[..., None] == edge_ids)          # (Qt, S, E_loc)
            rank = jnp.cumsum(am, axis=1) - 1
            pos = jnp.where(am, rank, s)
            sublists = jnp.full((qt, e_loc, s, 2), -1, jnp.int32)
            qq = jnp.broadcast_to(
                jnp.arange(qt, dtype=jnp.int32)[:, None, None], (qt, s, e_loc))
            ee = jnp.broadcast_to(
                jnp.arange(e_loc, dtype=jnp.int32)[None, None, :],
                (qt, s, e_loc))
            sidv = jnp.stack([matched.sid_hi, matched.sid_lo],
                             axis=-1)                             # (Qt, S, 2)
            sidv = jnp.broadcast_to(sidv[:, :, None, :], (qt, s, e_loc, 2))
            sublists = sublists.at[qq, ee, pos].set(sidv, mode="drop")
            sublist_len = jnp.sum(am, axis=1).astype(jnp.int32)   # (Qt, E_loc)
        with jax.named_scope("query.plan"):
            ovf = matched.overflow
            shards_matched = jnp.sum(matched.valid, axis=-1)
            # Degraded-query accounting (replicated metadata, like
            # planning): dead replica slots over the matched set, and the
            # planner-derived completeness bound — matched shards whose
            # replicas all died are unassignable (assignment == -1) and
            # provably missing from the result. Overflow clips the tracked
            # set, so the bound is unknown.
            reps = matched.replicas
            dead_slot = (matched.valid[..., None] & (reps >= 0)
                         & ~jnp.take(alive, jnp.clip(reps, 0), axis=0))
            replicas_lost = jnp.sum(dead_slot, axis=(1, 2)).astype(jnp.int32)
            assigned_n = jnp.sum(matched.valid & (assignment >= 0), axis=-1)
            bound = jnp.where(shards_matched > 0,
                              assigned_n / jnp.maximum(shards_matched, 1), 1.0)
            bound = jnp.where(ovf, jnp.nan, bound).astype(jnp.float32)
        with jax.named_scope("query.scan"):
            partials = scan_engine(state.tup_f, state.tup_sid,
                                   state.tup_count, p, sublists, sublist_len,
                                   use_kernel, interpret,
                                   channels=agg.channels,
                                   valid_c=cfg.tuple_capacity)
        outs.append((partials, sublist_len, ovf, shards_matched,
                     replicas_lost, bound))

    if len(outs) == 1:
        partials, sublist_len, ovf, shards_matched, replicas_lost, bound = \
            outs[0]
    else:
        cat = lambda xs: jnp.concatenate(xs, axis=0)
        partials = tuple(cat([o[0][i] for o in outs]) for i in range(4))
        sublist_len, ovf, shards_matched, replicas_lost, bound = (
            cat([o[j] for o in outs]) for j in range(1, 6))
    return partials, sublist_len, (lookup_mask, broadcast, ovf, shards_matched,
                                   replicas_lost, bound)


def finalize_query(partials, sublist_len, lookup_mask, broadcast, overflow,
                   shards_matched, replicas_lost, completeness_bound):
    """Final (Q, K, E) -> (Q[, K]) combine shared by the 1-device and sharded
    paths (under the federated runtime, this is the only
    tuple-volume-independent reduction crossing devices). ``partials`` are
    full-E per-edge aggregates: channel-independent (Q, E) count plus
    per-channel (Q, K, E) value aggregates; single-channel specs (K == 1)
    squeeze to the classic (Q,) result shapes. ``mean`` is derived here from
    the combined sum/count, so it adds no cross-device reduction of its own.

    Zero-match queries: the scan's +inf/-inf min/max accumulator sentinels
    (and the meaningless mean) are masked to NaN — they must never leak into
    ``QueryResult`` as if they were data.
    """
    with jax.named_scope("query.combine"):
        count, vsum, vmin, vmax = partials
        total = jnp.sum(count, axis=-1).astype(jnp.int32)        # (Q,)
        vsum_total = jnp.sum(vsum, axis=-1)                      # (Q, K)
        vmin_total = jnp.min(vmin, axis=-1)
        vmax_total = jnp.max(vmax, axis=-1)
        some = (total > 0)[:, None]                              # (Q, 1)
        vmin_total = jnp.where(some, vmin_total, jnp.nan)
        vmax_total = jnp.where(some, vmax_total, jnp.nan)
        vmean = jnp.where(some, vsum_total / jnp.maximum(total, 1)[:, None],
                          jnp.nan)
        if vsum_total.shape[-1] == 1:    # single-channel: classic (Q,)
            vsum_total, vmin_total, vmax_total, vmean = (
                a[:, 0] for a in (vsum_total, vmin_total, vmax_total, vmean))
        result = QueryResult(
            count=total,
            vsum=vsum_total,
            vmin=vmin_total,
            vmax=vmax_total,
            overflow=overflow,
            vmean=vmean,
            completeness_bound=completeness_bound,
            replicas_lost=replicas_lost,
        )
        info = QueryInfo(
            lookup_edges=jnp.sum(lookup_mask, axis=-1),
            subquery_edges=jnp.sum(sublist_len != 0, axis=-1),
            shards_matched=shards_matched,
            max_shards_per_edge=jnp.max(jnp.abs(sublist_len), axis=-1),
            broadcast=broadcast,
            replicas_lost=replicas_lost,
            completeness_bound=completeness_bound,
        )
        return result, info


@partial(jax.jit, static_argnums=(0, 5, 6, 7))
def _query_step_jit(cfg: StoreConfig, state: StoreState, pred: QueryPred,
                    alive: jnp.ndarray, key: jax.Array,
                    use_kernel: bool = False,
                    interpret: Optional[bool] = None,
                    channels: Tuple[int, ...] = (0,)):
    edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
    partials, sublist_len, meta_info = \
        query_local(cfg, state, pred, alive, key, edge_ids,
                    use_kernel=use_kernel, interpret=interpret,
                    agg=AggSpec(channels=channels))
    return finalize_query(partials, sublist_len, *meta_info)


def _query(cfg: StoreConfig, state: StoreState, pred: QueryPred,
           alive: jnp.ndarray, key: jax.Array, use_kernel: bool = False,
           interpret: Optional[bool] = None, agg: AggSpec = AggSpec()):
    """1-device query body shared by the ``AerialDB`` facade and the
    deprecated ``query_step`` shim. Only ``agg.channels`` reaches the jit
    cache key — varying the requested ops never recompiles."""
    agg.validate_for(cfg)
    return _query_step_jit(cfg, state, pred, alive, key, use_kernel,
                           interpret, agg.channels)


def query_step(cfg: StoreConfig, state: StoreState, pred: QueryPred,
               alive: jnp.ndarray, key: jax.Array, use_kernel: bool = False,
               interpret: Optional[bool] = None, agg: AggSpec = AggSpec()):
    """Decentralized query execution (paper Fig 4): index lookup -> planning
    -> per-edge sub-queries -> combine. The 1-device special case of
    ``query_local``. Returns (QueryResult, QueryInfo).

    .. deprecated:: kept as a thin shim over the same body the
       ``repro.api.AerialDB`` facade drives; prefer ``AerialDB.query``.
    """
    _warn_deprecated("query_step", "repro.api.AerialDB.query")
    return _query(cfg, state, pred, alive, key, use_kernel, interpret, agg)
