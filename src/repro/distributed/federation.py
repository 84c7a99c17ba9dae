"""Sharded federated runtime: the datastore partitioned over a device mesh.

The paper's federation story at device scale — a datastore mesh whose
*edge-bearing axes* (``distributed.sharding.mesh_edge_axes``) partition the
logical edge axis: the 1-D ``("edge",)`` mesh (``launch.mesh.make_edge_mesh``)
where each device plays a block of ``E / n_devices`` ground edge servers, or
the 2-D ``("fleet", "edge")`` cross-host mesh (``launch.mesh.make_fleet_mesh``)
where each host owns one fleet partition and the edge axis splits over the
axis product, fleet-major. Each device holds exactly its edges' slice of
every ``StoreState`` array (leading logical-E dim; contract in
``distributed.sharding.store_partition_specs``). The shard-local bodies in
``core.datastore`` (``insert_local`` / ``query_local``) run under ``shard_map``
with the axis-parameterized ``EdgeCollectives`` bundle built here
(``make_collectives``), so the tuple scatter, the index writes, and the
per-edge predicate scan are all device-local; cross-device traffic is
tuple-volume independent:

  * insert — one (E,) all-gather of per-edge retention watermarks (entries
    name replica edges anywhere, so retirement needs every edge's watermark);
  * query  — a *hierarchical* merge of each device's local top-S candidate
    shards (``_merge_matched``): intra-fleet all-gather + top-S reduce first
    (on-host under the fleet mesh), then the inter-fleet collective over the
    already-reduced S-sized set — re-deduplicated replicated at each level
    (``index.dedup_matched``: distributed top-k, bit-identical to the
    single-device lookup), then the final (Q, E) -> (Q,) combine of per-edge
    partial aggregates. On multi-fleet meshes the query batch is split into
    double-buffered tiles (``query_local``'s ``overlap_tiles=2``): every
    tile's merge collectives are issued before any tile's log scan, so the
    cross-host exchange overlaps device-local compute — bitwise identical to
    the untiled plan (per-query folded planner keys);

everything else (placement, slice masks, planning) is metadata-scale and
recomputed replicated. ``tests/test_federation.py`` is the differential
harness proving the single-device, 1-D, and 2-D paths produce identical
results and states.

The jitted entry points are named for what they run — ``fed_insert``,
``fed_ingest_rounds``, ``ingest_rounds`` (one device) and ``fed_query`` —
and those names key the compile-count budgets in ``pyproject.toml`` and
label the programs (``jit_fed_query``, ...) in profiler traces.

Trace names of what the federation adds. Device scopes: the candidate
merge's all-gathers run under ``query.exchange`` (inside ``query.merge``,
whose re-deduplication stays ``query.merge``) and the watermark all-gather
under ``insert.exchange``; like ``core.datastore``'s scopes they label only
``op_name`` metadata. Host spans, tagged ``program=<jit name>``, in each
federated step: ``aerialdb.fed.check`` (mesh and batch checks, argument
preparation) and ``aerialdb.fed.call`` (the jitted call until it returns,
before its result is ready). The one-device paths carry none of them.

Sustained ingest goes through ``ingest_rounds`` — a fused ``lax.scan`` over
collection rounds that replaces Python-loop round-tripping (one dispatch, no
per-round host sync) and **donates** the store so the tuple ring is updated
in place instead of double-allocating (donation is a no-op on CPU backends).

Paper-scale runs (80 edges / 400 drones over 1/2/4/8 simulated devices and
1/2/4 fleets) are driven by ``benchmarks/fig7_insertion_scaling.py`` via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; the true
multi-process cross-host path (one process per fleet,
``launch.mesh.init_fleet_processes``) by ``benchmarks/multihost_smoke.py``.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.datastore import (AggSpec, EdgeCollectives, LOCAL_COLLECTIVES,
                                  StoreConfig, StoreState, check_batch_fits,
                                  finalize_query, insert_local, query_local)
from repro.core.index import MatchedShards, dedup_matched
from repro.core.placement import ShardMeta
from repro.distributed.sharding import (check_edge_partition, mesh_edge_axes,
                                        mesh_edge_devices, shard_store,
                                        store_partition_specs)

__all__ = [
    "federated_insert_step", "federated_query_step", "ingest_rounds",
    "make_collectives", "shard_store", "store_partition_specs",
]


def check_edge_mesh(cfg: StoreConfig, mesh: Mesh) -> int:
    """Validate the mesh against the deployment; returns the number of edge
    partitions (the edge-bearing axis product — device count for a pure
    datastore mesh)."""
    n_dev = mesh_edge_devices(mesh)  # raises without an "edge" axis
    check_edge_partition(cfg.n_edges, n_dev,
                         f"the edge mesh {dict(mesh.shape)}")
    if cfg.n_failure_domains > 1 and n_dev % cfg.n_failure_domains:
        raise ValueError(
            f"n_failure_domains={cfg.n_failure_domains} is incompatible with "
            f"an edge mesh of {n_dev} devices: each failure domain must be a "
            "whole number of device blocks (n_devices % n_failure_domains "
            "== 0), or two 'spread' replicas can silently share one device "
            "and a single device loss still takes out every copy. Use "
            f"n_failure_domains == {n_dev} (one domain per device), a "
            "divisor of it, or 1 to disable spreading.")
    return n_dev


def _replicated_like(tree):
    """A pytree of replicated PartitionSpecs matching ``tree``'s structure."""
    return jax.tree.map(lambda _: P(), tree)


def _insert_info_specs(scanned: bool, axes: tuple):
    """PartitionSpec tree for the insert info dict. Per-edge telemetry is
    sharded like the state (over the edge-bearing ``axes``); replicas and the
    (post-gather) watermark are replicated. ``scanned`` adds the leading
    rounds dim of ``ingest_rounds``."""
    per_edge = P(None, axes) if scanned else P(axes)
    return {
        "replicas": P(),
        "intake_per_edge": per_edge,
        "index_writes_per_edge": per_edge,
        "tuples_overwritten": per_edge,
        "tuples_dropped": per_edge,
        "index_entries_dropped": per_edge,
        "index_entries_retired": per_edge,
        "retention_watermark": P(),
    }


def _gather_watermark(wm_local: jnp.ndarray, axes: tuple) -> jnp.ndarray:
    """(E_local,) -> (E,) over the edge-bearing axis product. A tuple-axis
    all-gather concatenates major axis outermost — exactly the fleet-major
    edge-block order of the layout contract."""
    with jax.named_scope("insert.exchange"):
        return jax.lax.all_gather(wm_local, axes, axis=0, tiled=True)


def _merge_axis(local: MatchedShards, max_shards: int,
                axis: str) -> MatchedShards:
    """One merge level: all-gather each participant's top-S list along one
    mesh axis and re-deduplicate back down to top-S. The all-gathers run
    under ``query.exchange``, in the order the unlabelled code emits them."""
    cat = lambda x: jax.lax.all_gather(x, axis, axis=1, tiled=True)
    with jax.named_scope("query.exchange"):
        lists = [cat(x) for x in (local.valid, local.sid_hi, local.sid_lo,
                                  local.replicas)]
    merged = dedup_matched(*lists, max_shards)
    with jax.named_scope("query.exchange"):
        local_ovf = jax.lax.all_gather(local.overflow, axis, axis=0,
                                       tiled=False)
    any_local_ovf = jnp.any(local_ovf, axis=0)
    return merged._replace(overflow=merged.overflow | any_local_ovf)


def _merge_matched(local: MatchedShards, max_shards: int,
                   axes: tuple) -> MatchedShards:
    """Hierarchically merge per-device candidate lists into the global
    MatchedShards, innermost mesh axis first: on the ("fleet", "edge") mesh
    that is an intra-fleet all-gather + top-S reduce (on-host), then the
    inter-fleet collective over the already-reduced set — each level moves
    only S-sized lists, so the cross-host hop is max_shards wide regardless
    of fleet size.

    Exactness at every level: each participant contributes its top-
    ``max_shards`` distinct sids (in dedup_matched's canonical ascending
    order); gathering those lists and re-deduplicating yields exactly the
    flat-merge result — any sid missing from a contributed top list is
    preceded by >= max_shards smaller sids on that participant alone, so it
    cannot be in the merged top-``max_shards`` either; by the same argument
    the level outputs compose (distributed top-k transitivity). Overflow is
    the OR of participant overflows (a participant that clipped has
    > max_shards distinct sids globally too) and each level's merged count
    test — identical to the flat overflow bit.
    """
    for ax in reversed(axes):
        local = _merge_axis(local, max_shards, ax)
    return local


def make_collectives(axes: tuple) -> EdgeCollectives:
    """The axis-parameterized collective-hook bundle for the shard-local
    bodies: watermark all-gather over the edge-bearing axis product and the
    hierarchical candidate merge. ``axes`` comes from ``mesh_edge_axes``;
    the identity bundle (no mesh) is ``datastore.LOCAL_COLLECTIVES``."""
    axes = tuple(axes)
    return EdgeCollectives(
        gather_watermark=lambda wm: _gather_watermark(wm, axes),
        combine_matched=lambda matched, s: _merge_matched(matched, s, axes))


@lru_cache(maxsize=None)
def _insert_fn(cfg: StoreConfig, mesh: Mesh):
    axes = mesh_edge_axes(mesh)
    state_specs = store_partition_specs(axes)
    meta_specs = _replicated_like(ShardMeta(*ShardMeta._fields))
    collectives = make_collectives(axes)

    def body(state, payload, meta, alive, edge_ids):
        return insert_local(cfg, state, payload, meta, alive, edge_ids,
                            collectives=collectives)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, P(), meta_specs, P(), P(axes)),
        out_specs=(state_specs, _insert_info_specs(False, axes)),
        check_vma=False)

    def fed_insert(state, payload, meta, alive):
        edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
        return sharded(state, payload, meta, alive, edge_ids)

    return jax.jit(fed_insert)


def federated_insert_step(cfg: StoreConfig, state: StoreState,
                          payload: jnp.ndarray, meta: ShardMeta,
                          alive: jnp.ndarray, mesh: Mesh):
    """``insert_step`` over a datastore mesh: identical semantics, state
    sharded per ``store_partition_specs``, device-local tuple/index writes."""
    with TraceAnnotation("aerialdb.fed.check", program="fed_insert"):
        check_edge_mesh(cfg, mesh)
        check_batch_fits(cfg, payload.shape)
    with TraceAnnotation("aerialdb.fed.call", program="fed_insert"):
        return _insert_fn(cfg, mesh)(state, payload, meta, alive)


@lru_cache(maxsize=None)
def _ingest_fn(cfg: StoreConfig, mesh: Optional[Mesh]):
    meta_specs = _replicated_like(ShardMeta(*ShardMeta._fields))
    collectives = (make_collectives(mesh_edge_axes(mesh))
                   if mesh is not None else LOCAL_COLLECTIVES)

    def run(state, payloads, metas, alive, edge_ids):
        def round_body(carry, xs):
            payload, meta = xs
            return insert_local(cfg, carry, payload, meta, alive, edge_ids,
                                collectives=collectives)
        return jax.lax.scan(round_body, state, (payloads, metas))

    if mesh is None:
        def ingest_rounds(state, payloads, metas, alive):
            edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
            return run(state, payloads, metas, alive, edge_ids)
        return jax.jit(ingest_rounds, donate_argnums=(0,))

    axes = mesh_edge_axes(mesh)
    state_specs = store_partition_specs(axes)
    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(state_specs, P(), meta_specs, P(), P(axes)),
        out_specs=(state_specs, _insert_info_specs(True, axes)),
        check_vma=False)

    def fed_ingest_rounds(state, payloads, metas, alive):
        edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
        return sharded(state, payloads, metas, alive, edge_ids)

    return jax.jit(fed_ingest_rounds, donate_argnums=(0,))


def ingest_rounds(cfg: StoreConfig, state: StoreState, payloads, metas,
                  alive: jnp.ndarray, mesh: Optional[Mesh] = None):
    """Fused multi-round ingest: a single jitted ``lax.scan`` over N
    collection rounds (replaces Python-loop round-tripping in tests and
    benchmarks). The incoming ``state`` is **donated** — do not reuse it
    after the call (sustained ingest updates the tuple ring in place rather
    than double-allocating; donation is a no-op on CPU backends).

    Args:
      payloads: (N, B, R, 3+V) — N rounds of B shards.
      metas:    ShardMeta with (N, B) fields.
      alive:    (E,) availability mask, held fixed across the N rounds.
      mesh:     optional datastore mesh; None runs the 1-device jit path.

    Returns (state, info) with every info entry stacked over the N rounds.
    """
    if mesh is None:
        payloads, metas = _ingest_args(cfg, payloads, metas)
        return _ingest_fn(cfg, None)(state, payloads, metas, alive)
    with TraceAnnotation("aerialdb.fed.check", program="fed_ingest_rounds"):
        payloads, metas = _ingest_args(cfg, payloads, metas)
        check_edge_mesh(cfg, mesh)
    with TraceAnnotation("aerialdb.fed.call", program="fed_ingest_rounds"):
        return _ingest_fn(cfg, mesh)(state, payloads, metas, alive)


def _ingest_args(cfg: StoreConfig, payloads, metas):
    """``ingest_rounds``' arguments on the device, the batch checked."""
    payloads = jnp.asarray(payloads)
    metas = ShardMeta(*[jnp.asarray(x) for x in metas])
    check_batch_fits(cfg, payloads.shape[1:])
    return payloads, metas


@lru_cache(maxsize=None)
def _query_fn(cfg: StoreConfig, mesh: Mesh, use_kernel: bool,
              interpret: Optional[bool], channels: tuple):
    axes = mesh_edge_axes(mesh)
    state_specs = store_partition_specs(axes)
    collectives = make_collectives(axes)
    # Double-buffer the query batch on multi-fleet meshes so tile t+1's
    # cross-host merge overlaps tile t's device-local log scan; single-axis
    # meshes keep the untiled plan (the merge is on-host there).
    overlap_tiles = 2 if len(axes) > 1 else 1

    def body(state, pred, alive, key_data, edge_ids):
        key = jax.random.wrap_key_data(key_data)
        partials, sublist_len, meta_info = query_local(
            cfg, state, pred, alive, key, edge_ids,
            collectives=collectives,
            use_kernel=use_kernel, interpret=interpret,
            agg=AggSpec(channels=channels), overlap_tiles=overlap_tiles)
        return partials, sublist_len, meta_info

    # Partials: channel-independent (Q, E) count + per-channel (Q, K, E)
    # value aggregates — the edge axis stays last, so the final combine's
    # reduction axis is the (edge-bearing) mesh axes in both cases.
    partial_specs = (P(None, axes),) + (P(None, None, axes),) * 3

    def fed_query(state, pred, alive, key_data):
        edge_ids = jnp.arange(cfg.n_edges, dtype=jnp.int32)
        sharded = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, _replicated_like(pred), P(), P(),
                      P(axes)),
            out_specs=(partial_specs, P(None, axes),
                       (P(),) * 6),
            check_vma=False)
        partials, sublist_len, meta_info = \
            sharded(state, pred, alive, key_data, edge_ids)
        # The only tuple-volume-independent cross-device reduction: the final
        # (Q, E) combine over the sharded per-edge partials. The degraded-
        # query accounting (replicas_lost / completeness_bound) rides in
        # meta_info — computed replicated next to planning, like the rest.
        return finalize_query(partials, sublist_len, *meta_info)

    return jax.jit(fed_query)


def federated_query_step(cfg: StoreConfig, state: StoreState, pred,
                         alive: jnp.ndarray, key: jax.Array, mesh: Mesh,
                         use_kernel: bool = False,
                         interpret: Optional[bool] = None,
                         agg: AggSpec = AggSpec()):
    """``query_step`` over a datastore mesh: device-local index match + tuple
    scan, metadata-scale hierarchical candidate merge, replicated planning,
    and a final cross-device (Q, K, E) combine. ``agg`` (static) selects the
    sensor channel tuple and aggregate set; the device-local scan produces
    per-channel per-edge partials for every requested channel in ONE pass
    over the local log, and ``finalize_query``'s combine (including the
    derived mean) stays the only cross-device reduction. Only
    ``agg.channels`` keys the compiled-function cache — varying the ops
    projection is free. Returns (QueryResult, QueryInfo)."""
    with TraceAnnotation("aerialdb.fed.check", program="fed_query"):
        check_edge_mesh(cfg, mesh)
        agg.validate_for(cfg)
        key_data = jax.random.key_data(key)
    with TraceAnnotation("aerialdb.fed.call", program="fed_query"):
        return _query_fn(cfg, mesh, use_kernel, interpret, agg.channels)(
            state, pred, alive, key_data)
