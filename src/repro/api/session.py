"""``AerialDB``: the session facade over both runtimes.

One object owns everything callers used to hand-thread — ``StoreConfig``,
``StoreState``, the edge ``alive`` mask, the planner PRNG key, the scan-engine
flags — and transparently dispatches every operation to the single-device jit
path (``core.datastore``) or the shard_map federated path
(``distributed.federation``) depending on whether the session was opened on
an edge mesh. The two paths are differentially tested bit-identical
(``tests/test_federation.py``), so the dispatch is a pure deployment choice.

    db = AerialDB.open(cfg)                      # single device
    db = AerialDB.open(cfg, mesh=make_edge_mesh(4))   # 4-device federation
    db = AerialDB.open(cfg, mesh=make_fleet_mesh(2, 2))  # 2 fleets x 2 edges
    db.ingest_rounds(payloads, metas)
    res, info = db.query(Query().bbox(...).time(...).agg("mean", channel=2))
    db.fail_edges(1, 5); ...; db.recover_edges(1, 5)
    db.fail_device(0); ...; db.recover_device(0)      # whole failure domain
    db.partition([[0, 1], [2, 3]]); ...; db.heal()    # network partition

Failure-domain resilience (paper §4.5.3): ``fail_device`` / ``recover_device``
flip an entire contiguous device block of the edge axis at once — the unit
that actually fails when an edge *server* (one mesh device hosting
``E / n_devices`` edges) goes down. Recovery triggers an **anti-entropy
repair pass** (``core.repair``) by default: shards placed around the outage
are re-placed under the recovered mask, added replicas are backfilled with
tuples from surviving copies, and the recovered edges' indexes are
backfilled with every entry they missed — so a recovered edge serves
complete results instead of a silent lookup hole. The session keeps a
host-side **outage-epoch ledger** — every ``fail_*`` call opens an epoch
record ``(dead edges, fail_step)``, every ``recover_*`` call closes the
window at the current ingest step — and hands it to ``repair_state`` as an
``OutageLog``, so repair sweeps only the shards the recorded outages could
have touched (O(outage), not O(store); ``repair(full=True)`` forces the
full sweep). ``QueryInfo`` reports the degraded-query accounting
(``replicas_lost`` / ``completeness_bound``), and ``QueryResult.view``
carries both keys so applications see degradation without digging.

Fleet partition tolerance (PR 9): :meth:`partition` / :meth:`heal` model a
network partition — edges that are **unreachable but intact**, a ledger
state distinct from dead. The session keeps a ``reachable`` mask next to
``alive``; every placement/query/repair decision sees their conjunction
(:attr:`effective_alive`), so inserts re-route around the unreachable side
and queries surface the degradation through the same
``completeness_bound`` / ``replicas_lost`` accounting as a crash — but the
unreachable edges' state is never mutated, never backfilled, and never
reclaimed while the partition is open (their intact data may be the only
surviving copy). A heal closes an epoch window on the same outage ledger a
recovery does, so the incremental repair sweeps only shards ingested
*during* the partition plus those whose replicas straddled it — edges whose
data never died get no backfill.

See the package docstring (``repro.api``) for the facade-vs-local-bodies
layering contract.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.query import Query
from repro.core import datastore as _ds
from repro.core import repair as _repair
from repro.core.datastore import (AggSpec, LatestResult, QueryInfo,
                                  QueryResult, StoreConfig, StoreState,
                                  init_store)
from repro.core.index import QueryPred
from repro.core.placement import ShardMeta
from repro.distributed import federation as _fed
from repro.distributed.sharding import (device_edge_block, mesh_edge_devices,
                                        shard_store)

__all__ = ["AerialDB"]

Queryish = Union[Query, QueryPred, Tuple[QueryPred, AggSpec]]


class AerialDB:
    """An open AerialDB deployment: state + alive mask + key, one dispatch."""

    def __init__(self, cfg: StoreConfig, state: StoreState, alive, key,
                 mesh=None, use_kernel: bool = False,
                 interpret: Optional[bool] = None):
        """Wrap existing parts (the differential tests use this to adopt
        pre-loaded states); most callers want :meth:`open`."""
        if mesh is not None:
            _fed.check_edge_mesh(cfg, mesh)
        self._cfg = cfg
        self._state = state
        self._alive = jnp.asarray(alive, bool)
        self._key = key
        self._mesh = mesh
        self._use_kernel = use_kernel
        self._interpret = interpret
        self._last_repair: Optional[dict] = None
        self._n_queries = 0      # tags each ``aerialdb.query`` span
        # Outage-epoch ledger (see ``core.repair``): open records are
        # in-flight outages ``[dead edge set, fail_step]``; closed records
        # ``(recovered edge set, fail_step, recover_step)`` accumulate until
        # a repair consumes them. ``_pending_sids`` holds shards swept by a
        # repair that ran while other edges were still dead — they were
        # normalized to a *degraded* canonical placement and must be
        # re-swept until a repair completes with every edge alive.
        self._open_outages: list = []
        self._closed_outages: list = []
        self._pending_sids: set = set()
        # Fleet partition state (PR 9): ``_reachable`` marks edges the
        # session can still talk to — unreachable edges are intact (their
        # state is frozen, like dead ones) but excluded from placement,
        # query planning, and repair via ``effective_alive``. At most one
        # partition is open at a time; ``_partition`` records its
        # unreachable set + the step it opened at, closed onto the outage
        # ledger by :meth:`heal`.
        self._reachable = jnp.ones(cfg.n_edges, bool)
        self._partition: Optional[dict] = None
        # Ingest-time index-capacity drop watch: each insert's
        # (sid arrays, per-edge index_entries_dropped DEVICE array) is
        # recorded WITHOUT reading the array — reading would force a device
        # sync and break the ingest pipeline's double-buffering. The watch is
        # drained (arrays finally read, affected batches' sids folded into
        # ``_dropped_sids``) lazily: at repair/ledger-snapshot time, or once
        # the backlog passes a bound. ``_dropped_sids`` ride the OutageLog's
        # pending set so an INCREMENTAL repair re-attempts the dropped
        # entries exactly like a full sweep would.
        self._drop_watch: list = []
        self._dropped_sids: set = set()
        dead = np.nonzero(~np.asarray(self._alive, bool))[0]
        if dead.size:
            # Adopted state with unknown outage history: a fail_step of -1
            # covers every index entry, so the first repair after recovery
            # degenerates to (a correct) full-coverage sweep.
            self._open_outages.append([set(dead.tolist()), -1])

    @classmethod
    def open(cls, cfg: Optional[StoreConfig] = None, mesh=None, *,
             seed: int = 0, use_kernel: bool = False,
             interpret: Optional[bool] = None,
             **cfg_overrides) -> "AerialDB":
        """Open a fresh deployment.

        Args:
          cfg:   deployment config; None builds ``StoreConfig(**overrides)``.
          mesh:  optional datastore mesh — 1-D ``("edge",)``
                 (``launch.mesh.make_edge_mesh``) or 2-D ``("fleet", "edge")``
                 (``launch.mesh.make_fleet_mesh``): state is sharded per the
                 layout contract and every operation runs the federated
                 shard_map path. None = single-device jit path.
          seed:  planner PRNG seed (the facade owns and splits the key).
          use_kernel / interpret: scan-engine selection, as in
                 ``scan_engine`` (Pallas TPU kernel vs jnp engine).
          **cfg_overrides: with ``cfg=None``, StoreConfig fields; with a
                 config given, ``dataclasses.replace`` overrides.
        """
        if cfg is None:
            cfg = StoreConfig(**cfg_overrides)
        elif cfg_overrides:
            cfg = dataclasses.replace(cfg, **cfg_overrides)
        state = init_store(cfg)
        if mesh is not None:
            _fed.check_edge_mesh(cfg, mesh)
            state = shard_store(state, mesh)
        return cls(cfg, state, jnp.ones(cfg.n_edges, bool),
                   jax.random.key(seed), mesh=mesh, use_kernel=use_kernel,
                   interpret=interpret)

    # -- owned pieces (read-only views) -------------------------------------

    @property
    def cfg(self) -> StoreConfig:
        return self._cfg

    @property
    def state(self) -> StoreState:
        return self._state

    @property
    def alive(self) -> jnp.ndarray:
        return self._alive

    @property
    def reachable(self) -> jnp.ndarray:
        """(E,) bool — edges NOT cut off by an open :meth:`partition`.
        Orthogonal to :attr:`alive`: an edge can be dead, unreachable, or
        both; only ``alive & reachable`` edges serve."""
        return self._reachable

    @property
    def effective_alive(self) -> jnp.ndarray:
        """(E,) bool — the mask every placement/query/repair decision sees:
        ``alive & reachable``. Equals :attr:`alive` while no partition is
        open."""
        return self._alive & self._reachable

    @property
    def mesh(self):
        return self._mesh

    # -- ingest -------------------------------------------------------------

    # Drop-watch backlog bound: past this many unread insert telemetry
    # records, the next ingest drains them (each is months stale by then —
    # its compute long finished — so reading does not stall the device).
    _DROP_WATCH_MAX = 64

    def _watch_drops(self, sid_hi, sid_lo, dropped) -> None:
        """Record one ingest's (sids, device drop-count array) for lazy
        draining. ``sid_hi``/``sid_lo`` are host-side (N, B); ``dropped`` is
        the un-synced (N, E) device array from the insert info."""
        self._drop_watch.append((sid_hi, sid_lo, dropped))
        if len(self._drop_watch) > self._DROP_WATCH_MAX:
            self._drain_drop_watch()

    def _drain_drop_watch(self) -> None:
        """Read the watched drop counters (device sync point) and fold the
        sids of every round that dropped index entries into
        ``_dropped_sids``. Superset semantics are fine: sweeping a batch-mate
        whose entry landed is a canonical-placement no-op."""
        for hi, lo, dropped in self._drop_watch:
            d = np.asarray(dropped)
            for rnd in np.nonzero(d.sum(axis=1) > 0)[0]:
                self._dropped_sids.update(
                    _repair.sid_key(int(h), int(l))
                    for h, l in zip(hi[rnd], lo[rnd]))
        self._drop_watch = []

    def insert(self, payload, meta: ShardMeta) -> dict:
        """Insert one batch of B shards (R tuples each); returns the info
        dict (replicas, per-edge intake/index telemetry)."""
        with TraceAnnotation("aerialdb.insert"):
            payload = jnp.asarray(payload)
            sid_hi = np.asarray(meta.sid_hi)[None]   # host copies of INPUTS
            sid_lo = np.asarray(meta.sid_lo)[None]   # — no device-sync hazard
            meta = ShardMeta(*[jnp.asarray(f) for f in meta])
            mask = self.effective_alive
            if self._mesh is None:
                self._state, info = _ds._insert(self._cfg, self._state,
                                                payload, meta, mask)
            else:
                self._state, info = _fed.federated_insert_step(
                    self._cfg, self._state, payload, meta, mask, self._mesh)
            self._watch_drops(sid_hi, sid_lo,
                              info["index_entries_dropped"][None])
            return info

    def ingest_rounds(self, payloads, metas) -> dict:
        """Fused multi-round ingest (one ``lax.scan`` dispatch, donated
        state); returns the info dict stacked over rounds."""
        with TraceAnnotation("aerialdb.ingest_rounds"):
            sid_hi = np.asarray(metas.sid_hi)        # (N, B) host copies
            sid_lo = np.asarray(metas.sid_lo)
            self._state, info = _fed.ingest_rounds(
                self._cfg, self._state, payloads, metas,
                self.effective_alive, mesh=self._mesh)
            self._watch_drops(sid_hi, sid_lo, info["index_entries_dropped"])
            return info

    # -- query --------------------------------------------------------------

    def _compile(self, q: Queryish,
                 agg: Optional[AggSpec]) -> Tuple[QueryPred, AggSpec]:
        if isinstance(q, Query):
            if agg is not None:
                raise ValueError(
                    "pass the AggSpec on the builder (.agg(...)) OR as the "
                    "agg= override for a raw QueryPred, not both.")
            return q.build()
        if isinstance(q, QueryPred):
            return q, agg if agg is not None else AggSpec()
        if isinstance(q, tuple) and len(q) == 2 \
                and isinstance(q[0], QueryPred) and isinstance(q[1], AggSpec):
            if agg is not None:
                raise ValueError("q already carries an AggSpec; drop agg=.")
            return q
        raise TypeError(
            f"cannot query with {type(q).__name__}: pass a Query builder, a "
            "QueryPred (e.g. make_pred(...) or Query.batch(...)), or a "
            "(QueryPred, AggSpec) pair.")

    def query(self, q: Queryish, *, agg: Optional[AggSpec] = None,
              key: Optional[jax.Array] = None
              ) -> Tuple[QueryResult, QueryInfo]:
        """Run a query batch against the deployment.

        Args:
          q:    a ``Query`` builder, a batched ``QueryPred``
                (``Query.batch`` / ``make_pred``), or a
                ``(QueryPred, AggSpec)`` pair.
          agg:  AggSpec override for a raw QueryPred (channel(s) + ops). A
                multi-channel spec (``AggSpec(channels=(0, 2))``) aggregates
                every listed channel in the SAME single scan of the log and
                widens the value aggregates to (Q, K).
          key:  explicit planner PRNG key; None draws from the session key
                (each query consumes a fresh split).

        Returns ``(QueryResult, QueryInfo)``; project the requested
        aggregates with ``result.view(agg_spec)``. A ``Query().latest()``
        builder short-circuits to :meth:`latest` and returns its
        ``LatestResult`` directly (no scan, no planner, no ``QueryInfo``).

        Profiler spans: ``aerialdb.query`` (tagged ``q=<n>``, the session's
        query count) around ``aerialdb.query.prepare`` (builder, AggSpec
        check, key split, alive mask) and ``aerialdb.query.dispatch`` (the
        jitted call until it returns, before the answer is ready).
        """
        self._n_queries += 1
        with TraceAnnotation("aerialdb.query", q=self._n_queries):
            if isinstance(q, Query) and q.want_latest:
                if agg is not None:
                    raise ValueError(
                        "latest() queries take no AggSpec: the hot-cache "
                        "read returns raw (D, 3+V) records, not aggregates.")
                return self.latest()
            with TraceAnnotation("aerialdb.query.prepare"):
                pred, spec = self._compile(q, agg)
                spec.validate_for(self._cfg)
                if key is None:
                    self._key, key = jax.random.split(self._key)
                mask = self.effective_alive
            with TraceAnnotation("aerialdb.query.dispatch"):
                if self._mesh is None:
                    return _ds._query(self._cfg, self._state, pred, mask, key,
                                      self._use_kernel, self._interpret, spec)
                return _fed.federated_query_step(
                    self._cfg, self._state, pred, mask, key, self._mesh,
                    use_kernel=self._use_kernel, interpret=self._interpret,
                    agg=spec)

    def latest(self) -> LatestResult:
        """Latest-per-drone hot-cache read (paper §4.4 near-real-time path):
        the O(drones) ``LatestResult`` — newest (max-t) record, last-seen
        ingest step, and validity per drone id — straight from the
        replicated cache state, bypassing the log scan, the index, and the
        planner. Identical on both runtimes (the cache is replicated across
        the mesh and updated identically on every device — differential
        harness coverage in ``tests/test_federation.py``); staleness bound:
        exact up to the last *completed* insert (records still in an ingest
        pipeline's pending buffer are overlaid by
        ``IngestPipeline.latest()``)."""
        if self._cfg.max_drones == 0:
            raise ValueError(
                "the latest-per-drone cache is disabled: open the session "
                "with StoreConfig.max_drones >= the fleet's highest drone id "
                "+ 1 to track an O(drones) hot cache (drone id = sid_hi).")
        seen = self._state.latest_seen
        return LatestResult(record=self._state.latest_f, last_seen=seen,
                            valid=seen >= 0)

    # -- membership / failure domains ---------------------------------------

    def _edge_ids(self, edges) -> np.ndarray:
        """Normalize + validate edge ids **eagerly** on host.

        JAX scatter semantics silently clamp out-of-range indices, so the
        historical ``.at[ids].set(...)`` membership flips turned
        ``fail_edges(cfg.n_edges)`` into "mark the LAST edge dead" instead
        of an error. Every membership id is therefore validated here against
        ``cfg.n_edges`` (negatives, overflow, duplicates all raise) before
        any device op sees it.
        """
        ids = np.asarray(
            edges[0] if len(edges) == 1 and not isinstance(edges[0], int)
            else edges, np.int64).reshape(-1)
        if ids.size == 0:
            raise ValueError("no edge ids given: pass at least one edge id "
                             "(fail_edges(3) or fail_edges([3, 5])).")
        e = self._cfg.n_edges
        bad = ids[(ids < 0) | (ids >= e)]
        if bad.size:
            raise ValueError(
                f"edge id(s) {sorted(set(bad.tolist()))} out of range: this "
                f"deployment has n_edges={e} (valid ids 0..{e - 1}); JAX "
                "scatter clamping would silently retarget them.")
        if np.unique(ids).size != ids.size:
            dup = sorted({int(i) for i in ids
                          if (ids == i).sum() > 1})
            raise ValueError(
                f"duplicate edge id(s) {dup}: membership flips take each "
                "edge at most once.")
        return ids.astype(np.int32)

    def _device_edges(self, device: int) -> np.ndarray:
        """Resolve a failure-domain id to its contiguous edge block:
        ``cfg.n_failure_domains`` blocks when configured (> 1), else the
        session mesh's device blocks (the layout contract)."""
        n = self._cfg.n_failure_domains
        if n == 1 and self._mesh is not None:
            n = mesh_edge_devices(self._mesh)
        if n == 1:
            raise ValueError(
                "no failure domains to address: open the session on an edge "
                "mesh or set StoreConfig.n_failure_domains > 1 (device-level "
                "failures flip one contiguous block of E / n_domains edges).")
        return np.asarray(device_edge_block(self._cfg.n_edges, n, device),
                          np.int32)

    def fail_edges(self, *edges) -> "AerialDB":
        """Mark edges dead (paper §4.5.3 resilience shape): subsequent
        inserts skip them, queries re-plan around them; ids are validated
        eagerly (out-of-range / duplicate ids raise). Each call opens an
        outage-epoch record ``(newly dead edges, current step)`` on the
        session ledger so the eventual repair can sweep O(outage).

        Double-open semantics are **merge**: failing an already-dead edge
        changes nothing — the edge stays covered by the epoch record its
        ORIGINAL failure opened (the earlier fail step is the one the
        outage window must date from), no second record is opened for it,
        and a call whose every id is already dead is a pure no-op. Failing
        an unreachable (partitioned) edge is legal and independent: death
        and reachability compose via :attr:`effective_alive`."""
        ids = self._edge_ids(edges)
        newly_dead = ids[np.asarray(self._alive)[ids]]
        self._alive = self._alive.at[ids].set(False)
        if newly_dead.size:
            self._open_outages.append(
                [set(int(i) for i in newly_dead), int(self._state.steps)])
        return self

    def recover_edges(self, *edges, repair: bool = True) -> "AerialDB":
        """Bring failed edges back (their state was retained while dead).

        Closes the recovered edges' outage-epoch windows at the current
        ingest step. By default a recovery then triggers the incremental
        anti-entropy :meth:`repair` pass, so shards ingested during the
        outage are re-placed onto the recovered edges and their index
        entries/tuples backfilled — without it, a recovered edge answers
        index lookups from a table that is silently missing the whole
        outage window. Pass ``repair=False`` to defer (e.g. when recovering
        several domains and repairing once): the closed windows stay on the
        ledger until a repair consumes them.

        Double-close semantics are **no-op**: recovering an edge that is
        already alive closes nothing, and a call whose every id is alive
        leaves the session bitwise untouched — no window closes AND the
        implicit repair is skipped (it would otherwise consume closed
        windows deferred by an earlier ``repair=False`` recovery as a side
        effect of a do-nothing call). Deferred windows stay on the ledger
        for an explicit :meth:`repair` or the next real recovery.
        """
        ids = self._edge_ids(edges)
        newly_alive = set(int(i) for i in ids[~np.asarray(self._alive)[ids]])
        if not newly_alive:
            return self
        self._alive = self._alive.at[ids].set(True)
        recover_step = int(self._state.steps)
        for rec in self._open_outages:
            inter = rec[0] & newly_alive
            if inter:
                self._closed_outages.append(
                    (frozenset(inter), rec[1], recover_step))
                rec[0] -= inter
                newly_alive -= inter
        self._open_outages = [r for r in self._open_outages if r[0]]
        if newly_alive:
            # Dead edges with no ledger record (defensive — adopted masks are
            # recorded by __init__): treat their history as unknown.
            self._closed_outages.append(
                (frozenset(newly_alive), -1, recover_step))
        if repair:
            self.repair()
        return self

    def fail_device(self, device: int) -> "AerialDB":
        """Kill a whole failure domain (one mesh device's contiguous edge
        block): the paper's edge-server loss, where every edge the device
        hosts disappears at once. Placement spreads replicas across domains
        (``StoreConfig.n_failure_domains``), so a single device loss leaves
        every shard reachable."""
        return self.fail_edges(self._device_edges(device))

    def recover_device(self, device: int, repair: bool = True) -> "AerialDB":
        """Bring a failed device's whole edge block back; runs the
        anti-entropy :meth:`repair` pass by default (see
        :meth:`recover_edges`)."""
        return self.recover_edges(self._device_edges(device), repair=repair)

    # -- fleet partitions (unreachable-but-intact) ---------------------------

    def partition(self, edge_groups) -> "AerialDB":
        """Open a fleet-level network partition (paper's intermittent
        cellular links): split the edges into disjoint connectivity groups;
        the session (coordinator) stays with the FIRST group, every edge in
        the other groups becomes **unreachable but intact** — a ledger state
        distinct from dead. Unreachable edges are excluded from placement,
        query planning, and repair (via :attr:`effective_alive`) but their
        state is never mutated: the data on the far side of a partition is
        not lost, merely invisible, and must never be backfilled over.

        ``edge_groups`` is a sequence of edge-id groups (a flat list of ids
        is shorthand for one group). Edges named in no group implicitly join
        the coordinator side; with a single group given, the complement
        becomes the unreachable side. Groups must be disjoint, and the split
        must actually separate something (both sides non-empty) — degenerate
        partitions raise. At most one partition is open at a time: nested
        partitions raise (``heal()`` first); :meth:`heal` on a healed
        session is a no-op, so open/close is deterministic like the
        fail/recover ledger. Dead edges may appear in any group — death and
        reachability compose.
        """
        if self._partition is not None:
            raise ValueError(
                "a fleet partition is already open (unreachable edges "
                f"{sorted(self._partition['unreachable'])}): heal() it "
                "first — nested/overlapping partitions are not modeled.")
        groups = list(edge_groups)
        if groups and isinstance(groups[0], (int, np.integer)):
            groups = [groups]                   # flat id list = one group
        if not groups:
            raise ValueError("partition() needs at least one edge group.")
        ids = [self._edge_ids((g,)) if len(g) else np.empty(0, np.int32)
               for g in groups]           # empty group: names no edges
        flat = np.concatenate(ids)
        if np.unique(flat).size != flat.size:
            dup = sorted({int(i) for i in flat if (flat == i).sum() > 1})
            raise ValueError(
                f"edge id(s) {dup} appear in more than one partition group: "
                "connectivity groups must be disjoint.")
        if len(ids) == 1:
            unreachable = np.setdiff1d(
                np.arange(self._cfg.n_edges, dtype=np.int32), ids[0])
        else:
            unreachable = np.concatenate(ids[1:])
        if unreachable.size == 0:
            raise ValueError(
                "partition separates nothing: every edge ends up on the "
                "coordinator side. Name at least one edge in a non-first "
                "group (or pass a single group that excludes some edges).")
        if unreachable.size == self._cfg.n_edges:
            raise ValueError(
                "partition leaves the coordinator no reachable edges: the "
                "first group (the session's side) must keep at least one.")
        self._reachable = self._reachable.at[unreachable].set(False)
        self._partition = {
            "unreachable": set(int(i) for i in unreachable),
            "step": int(self._state.steps),
            "groups": tuple(tuple(int(i) for i in g) for g in ids)}
        return self

    def heal(self, *, repair: bool = True) -> "AerialDB":
        """Close the open partition: every edge becomes reachable again and
        the partition's epoch window ``(open step, current step]`` closes
        onto the SAME outage ledger a recovery uses — so the default
        incremental :meth:`repair` sweeps exactly the shards ingested while
        the fleet was split (they were placed around the unreachable side
        and owe it replicas/entries) plus those whose replicas straddle any
        still-dead edges. Edges whose data never died get no backfill: a
        shard placed before the partition, with all its replicas intact on
        the far side, is a full-sweep no-op. ``repair=False`` defers, like
        :meth:`recover_edges`. Healing a healed session is a no-op."""
        if self._partition is None:
            return self
        rec = self._partition
        self._partition = None
        self._reachable = jnp.ones(self._cfg.n_edges, bool)
        self._closed_outages.append(
            (frozenset(rec["unreachable"]), rec["step"],
             int(self._state.steps)))
        if repair:
            self.repair()
        return self

    def ledger(self) -> dict:
        """Machine-readable snapshot of the session's failure ledger (the
        chaos engine's telemetry surface): open outage records, closed
        (unconsumed) epoch windows, the open partition if any, and the
        pending/dropped sweep debts. Draining the drop watch here is a
        device sync point — this is a control-plane probe, not a hot
        path."""
        self._drain_drop_watch()
        return {
            "open_outages": [(sorted(rec[0]), int(rec[1]))
                             for rec in self._open_outages],
            "closed_windows": [(sorted(eds), int(f), int(r))
                               for eds, f, r in self._closed_outages],
            "partition": (None if self._partition is None else
                          {"unreachable":
                           sorted(self._partition["unreachable"]),
                           "step": self._partition["step"]}),
            "pending_sids": len(self._pending_sids),
            "dropped_sids": len(self._dropped_sids),
        }

    def _outage_log(self) -> "_repair.OutageLog":
        """Snapshot the session ledger as the ``OutageLog`` driving the
        incremental sweep (sorted — deterministic across differential
        runtimes). ``affected_edges`` carries only the OPEN outages' edges —
        the ones still dead now: a shard whose replicas touch an edge that
        failed AND already recovered is a full-sweep no-op (its stored
        placement equals the canonical one under the restored mask), so
        selecting it would make the sweep O(store) again. Shards *ingested*
        while that edge was away are what its closed window selects. The
        pending set folds in ``_dropped_sids`` (batches whose index entries
        were dropped at ingest by a momentarily-full table) so the
        incremental sweep re-attempts them like ``repair(full=True)``.
        An OPEN partition's unreachable edges ride ``affected_edges`` just
        like still-dead ones — a mid-partition repair re-places shards
        around them under the effective mask — and its window closes onto
        the same ledger at heal, so the reachable dimension needs no new
        OutageLog field."""
        self._drain_drop_watch()
        affected = set()
        for rec in self._open_outages:
            affected |= rec[0]
        if self._partition is not None:
            affected |= self._partition["unreachable"]
        return _repair.OutageLog(
            windows=tuple(sorted((int(f), int(r))
                                 for _eds, f, r in self._closed_outages)),
            affected_edges=tuple(sorted(affected)),
            pending_sids=tuple(sorted(self._pending_sids
                                      | self._dropped_sids)))

    def repair(self, *, full: bool = False) -> dict:
        """Anti-entropy re-replication sweep (``core.repair.repair_state``):
        re-derive swept shards' canonical placement under the current alive
        mask, rewrite stale replica sets, backfill tuples onto added
        replicas from surviving copies, reclaim stale ring slots on edges
        dropped by re-placement, and backfill missing index entries (the
        recovered-edge lookup hole). By default the sweep is **incremental**
        — driven by the session's outage-epoch ledger, it touches only
        shards the recorded outages could have affected, so an empty ledger
        is a telemetry-only no-op (``shards_swept == 0``); ``full=True``
        forces the classic every-tracked-shard sweep. A completed repair
        consumes the ledger's closed windows. Host-side control-plane
        operation — deterministic, so differential runtimes stay bitwise
        identical — and **single-process only**: the host gather assumes it
        sees the whole store (ROADMAP, cross-host mesh contract), so
        multi-process sessions raise instead of silently diverging per
        process. Returns the repair telemetry dict (also kept on
        :attr:`last_repair`)."""
        if jax.process_count() > 1:
            raise NotImplementedError(
                "AerialDB.repair() is single-process only: it gathers the "
                "full store to the host, which under a multi-process mesh "
                f"(jax.process_count()={jax.process_count()}) would repair "
                "each process's addressable slice independently and diverge "
                "the replicated state. See ROADMAP 'Cross-host mesh "
                "contract' — run repair from a single-process session, or "
                "defer with recover_edges(..., repair=False).")
        outage = None if full else self._outage_log()
        # Repair sees the EFFECTIVE mask: unreachable edges are treated
        # exactly like dead ones — never read as a source, never written,
        # never reclaimed — because their intact far-side state may be the
        # only surviving copy of a shard.
        state, info = _repair.repair_state(self._cfg, self._state,
                                           self.effective_alive,
                                           outage=outage)
        self._state = (shard_store(state, self._mesh)
                       if self._mesh is not None else state)
        # Ledger consumption: closed windows are now repaired; shards swept
        # under a still-degraded mask (dead OR unreachable edges remain)
        # stay pending until a repair completes with every edge effective.
        swept_keys = info.pop("_swept_keys")
        self._closed_outages = []
        if bool(np.asarray(self.effective_alive).all()):
            self._pending_sids = set()
        else:
            self._pending_sids |= set(swept_keys)
        # Dropped-entry ledger: a sweep that re-attempted every watched sid
        # without re-dropping (tables have room again) settles the debt; a
        # sweep that dropped again keeps them pending for the next repair.
        self._drain_drop_watch()
        if info.get("entries_dropped", 0) == 0:
            self._dropped_sids = set()
        self._last_repair = info
        return info

    @property
    def last_repair(self) -> Optional[dict]:
        """Telemetry of the most recent :meth:`repair` pass (None before)."""
        return self._last_repair
