"""Streaming ingest demo: ragged drone telemetry through ``IngestPipeline``.

A fleet of drones reports position + sensor records as they arrive — out of
order, with duplicate re-sends, seq gaps, and partial payloads. The pipeline
dedups and coalesces them into the store's device-shaped shard batches
(double-buffered against the device scan), and the O(drones) latest-per-drone
hot cache answers "where is every drone right now" without touching the log
scan — including records still in flight, via the pending overlay.

    PYTHONPATH=src python examples/streaming_ingest_demo.py

(The XLA flag below must be set before jax is imported: jax locks the host
device count at backend initialization.)
"""

import os

_FORCE = "--xla_force_host_platform_device_count"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FORCE}=4").strip()

import numpy as np            # noqa: E402

from repro.api import AerialDB, Query, StoreConfig                   # noqa: E402
from repro.data.synthetic import CityConfig, make_sites              # noqa: E402
from repro.ingest import IngestPipeline                              # noqa: E402
from repro.launch.mesh import make_edge_mesh                         # noqa: E402
from repro.launch.compile_cache import enable_compile_cache          # noqa: E402

D, R, ROUNDS = 24, 4, 3       # drones, records per shard, telemetry rounds


def main():
    n_edges = 8
    sites = make_sites(n_edges, CityConfig(), seed=3)
    cfg = StoreConfig(n_edges=n_edges, sites=tuple(map(tuple, sites.tolist())),
                      tuple_capacity=1 << 12, index_capacity=512,
                      records_per_shard=R, max_drones=D)
    db = AerialDB.open(cfg, mesh=make_edge_mesh(4))
    pipe = IngestPipeline(db)
    rng = np.random.default_rng(11)
    city = CityConfig()

    for rnd in range(ROUNDS):
        # Every drone emits R sequenced records...
        drone = np.repeat(np.arange(D), R)
        seq = np.tile(np.arange(rnd * R, (rnd + 1) * R), D)
        n = drone.size
        t = seq + rng.uniform(0, 0.5, n)
        lat = rng.uniform(city.lat_min, city.lat_max, n)
        lon = rng.uniform(city.lon_min, city.lon_max, n)
        vals = rng.normal(size=(n, cfg.n_values))
        vals[rng.random(n) < 0.1, 2:] = np.nan       # partial payloads
        # ...but the uplink drops some, re-sends others, and shuffles all.
        idx = np.nonzero(rng.random(n) >= 0.05)[0]
        idx = np.concatenate([idx, idx[rng.random(idx.size) < 0.08]])
        rng.shuffle(idx)
        pipe.submit_arrays(drone[idx], seq[idx], t[idx], lat[idx], lon[idx],
                           vals[idx])
        fl = pipe.flush()                            # full shards -> device
        c = pipe.counters
        print(f"round {rnd}: submitted={idx.size} accepted={c['accepted']} "
              f"duplicate={c['duplicate']} partial={c['partial']} | "
              f"flushed {fl['flushed_records']} records "
              f"({fl['dispatches']} dispatches), pending={pipe.pending}")

    # Latest-per-drone: store hot cache (flushed) + pending overlay.
    record, valid = pipe.latest()
    print(f"latest(): {int(valid.sum())}/{D} drones tracked; drone 0 at "
          f"t={record[0, 0]:.2f} ({record[0, 1]:.4f}, {record[0, 2]:.4f})")
    # The same hot path through the query builder (flushed records only):
    res = db.query(Query().latest())
    print(f"Query().latest(): {int(np.asarray(res.valid).sum())}/{D} drones "
          f"queryable on-device")

    pipe.flush(drain=True)                           # ship sub-shard tails
    audit = pipe.reconcile()
    assert audit["ok"], audit
    print(f"reconcile: accepted={audit['accepted']} == "
          f"flushed={audit['flushed_records']} + pending={audit['pending']}; "
          f"stored={audit['stored_tuples']} == flushed x "
          f"replication={cfg.replication}  -> ok")


if __name__ == "__main__":
    enable_compile_cache()
    main()
