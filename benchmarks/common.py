"""Shared benchmark harness: timing, store construction, CSV emission.

Each fig*.py module mirrors one paper table/figure (DESIGN.md §7) and prints
``name,us_per_call,derived`` rows. A time is a measurement of the device it
ran on and of nothing else: every structured row carries ``device``
(``platform:device_kind:count`` as JAX reports it), and ``benchmarks.run``
prints the same string in a ``# device=`` line before the rows. A row from
the CPU backend or Pallas interpret mode says nothing about the TPU.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import AerialDB
from repro.core.datastore import StoreConfig, init_store, make_pred
from repro.data.synthetic import CityConfig, DroneFleet, make_sites, make_query_workload
from repro.distributed.federation import ingest_rounds, shard_store

ROWS = []   # structured rows, cleared per figure by run.py's --json machinery


def device_tag() -> str:
    """``platform:device_kind:count`` of the devices this process runs on."""
    d = jax.devices()
    return f"{d[0].platform}:{d[0].device_kind}:{len(d)}"


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append({"name": name, "us_per_call": round(float(us_per_call), 1),
                 "derived": derived, "device": device_tag()})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def timeit(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


def build_store(n_edges=20, n_drones=20, rounds=4, records=30, planner="min_shards",
                replication=3, use_index=True, tuple_capacity=1 << 15, seed=0,
                stagger_s=0.0, index_capacity=4096, retention_every=4,
                mesh=None, max_shards=512, n_failure_domains=1):
    """Stand up a loaded store. Ingest goes through the fused lax.scan driver
    (one dispatch for all rounds, donated state); pass ``mesh`` (an edge mesh)
    to load through the sharded federated runtime instead of 1-device jit.
    ``n_failure_domains`` > 1 turns on failure-domain replica spreading
    (fig14's device-failure rows)."""
    sites = make_sites(n_edges, CityConfig(), seed=3)
    cfg = StoreConfig(
        n_edges=n_edges, sites=tuple(map(tuple, sites.tolist())),
        tuple_capacity=tuple_capacity, index_capacity=index_capacity,
        max_shards_per_query=max_shards, records_per_shard=records,
        planner=planner, replication=replication, use_index=use_index,
        retention_every=retention_every, n_failure_domains=n_failure_domains)
    fleet = DroneFleet(n_drones, records_per_shard=records, seed=seed + 1,
                       stagger_s=stagger_s)
    state = init_store(cfg)
    if mesh is not None:
        state = shard_store(state, mesh)
    alive = jnp.ones(n_edges, bool)
    payloads, metas = fleet.next_rounds(rounds)
    state, _ = ingest_rounds(cfg, state, payloads, metas, alive, mesh=mesh)
    flat = payloads.reshape(-1, payloads.shape[-1])
    t_max = float(flat[:, 0].max())
    anchors = flat[:, :3]          # (t, lat, lon) of every inserted tuple
    return cfg, state, alive, fleet, t_max, anchors


def open_session(cfg, state, alive, seed=0, **kw) -> AerialDB:
    """Adopt a ``build_store`` state into an ``AerialDB`` session (the
    benchmarks' query/insert surface — no deprecated step shims)."""
    return AerialDB(cfg, state, alive, jax.random.key(seed), **kw)


def timed_insert(cfg, state, alive, payload, meta):
    """One facade insert from a FIXED pre-state (pure per call, so timeit
    re-runs measure the same work): returns the post-insert StoreState."""
    db = open_session(cfg, state, alive)
    db.insert(payload, meta)
    return db.state


def paper_workloads(t_max, n_queries=8, seed=11, anchors=None):
    """The paper's 9 workloads: {5min, 30min, 2h} x {200m, 1km, 5km}.

    ``anchors``: (N, 3) array of (t, lat, lon) of really-inserted tuples;
    windows are centered on sampled anchors (analysts query where drones
    flew), so small windows are non-empty as in the paper's trace-driven
    workload."""
    rng = np.random.default_rng(seed)
    out = {}
    for tname, tsec in [("5min", 300.0), ("30min", 1800.0), ("2h", 7200.0)]:
        for sname, skm in [("200m", 0.2), ("1km", 1.0), ("5km", 5.0)]:
            if anchors is None:
                w = make_query_workload(rng, n_queries, CityConfig(), t_max,
                                        skm, tsec)
            else:
                pick = anchors[rng.integers(0, len(anchors), n_queries)]
                deg = skm / 111.0
                w = dict(
                    lat0=(pick[:, 1] - deg / 2).astype(np.float32),
                    lat1=(pick[:, 1] + deg / 2).astype(np.float32),
                    lon0=(pick[:, 2] - deg / 2).astype(np.float32),
                    lon1=(pick[:, 2] + deg / 2).astype(np.float32),
                    t0=(pick[:, 0] - tsec / 2).astype(np.float32),
                    t1=(pick[:, 0] + tsec / 2).astype(np.float32))
            out[f"{tname}/{sname}"] = make_pred(
                q=n_queries, has_spatial=True, has_temporal=True, is_and=True,
                **w)
    return out
