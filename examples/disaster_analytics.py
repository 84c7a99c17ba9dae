"""End-to-end driver (the paper's kind is a datastore, so the end-to-end
scenario is serving spatio-temporal analytics under failures):

100 drones stream sensor shards into 20 edges while analyst clients issue
the paper's 9 query workloads; midway through, edges start failing. The
driver reports per-phase latency, completeness, and planner telemetry —
Fig 9 + Fig 14 as one live scenario.

    PYTHONPATH=src python examples/disaster_analytics.py
"""

import dataclasses
import time

import jax
import numpy as np

from repro.api import AerialDB
from repro.core.datastore import StoreConfig, make_pred
from repro.data.synthetic import CityConfig, DroneFleet, make_sites
from repro.launch.compile_cache import enable_compile_cache

# sized for this repo's 1-core CPU host; scale freely on real metal
N_EDGES, N_DRONES, ROUNDS = 20, 50, 5


def analyst_queries(anchors, rng, q=8, km=1.0, secs=1800.0):
    pick = anchors[rng.integers(0, len(anchors), q)]
    deg = km / 111.0
    return make_pred(
        q=q, lat0=pick[:, 1] - deg / 2, lat1=pick[:, 1] + deg / 2,
        lon0=pick[:, 2] - deg / 2, lon1=pick[:, 2] + deg / 2,
        t0=pick[:, 0] - secs / 2, t1=pick[:, 0] + secs / 2,
        has_spatial=True, has_temporal=True, is_and=True)


def main():
    rng = np.random.default_rng(0)
    sites = make_sites(N_EDGES, CityConfig(), seed=3)
    cfg = StoreConfig(n_edges=N_EDGES, sites=tuple(map(tuple, sites.tolist())),
                      tuple_capacity=1 << 15, index_capacity=4096,
                      max_shards_per_query=256, records_per_shard=30,
                      planner="min_shards")
    db = AerialDB.open(cfg)
    fleet = DroneFleet(N_DRONES, records_per_shard=30)

    anchors = []
    total_expected = 0
    for r in range(ROUNDS):
        payload, meta = fleet.next_shards()
        t0 = time.perf_counter()
        db.insert(payload, meta)
        jax.block_until_ready(db.state.tup_count)
        anchors.append(payload.reshape(-1, payload.shape[-1])[:, :3])
        total_expected += payload.shape[0] * payload.shape[1]

        # mid-mission failures: one edge dies at rounds 3 and 4 (§3.5.3)
        phase = "all-up"
        if r == 2:
            db.fail_edges(int(rng.integers(N_EDGES)))
            phase = "1 edge down"
        if r == 3:
            db.fail_edges(int(rng.integers(N_EDGES)))
            phase = "2 edges down"

        pred = analyst_queries(np.concatenate(anchors), rng)
        tq = time.perf_counter()
        result, qinfo = db.query(pred, key=jax.random.key(r))
        jax.block_until_ready(result.count)
        catch_all = make_pred(q=1, t0=0.0, t1=1e9, has_temporal=True)
        # audit query touches every shard: use the vectorized random planner
        # (MinShards' greedy loop is for normal-sized result sets)
        audit_db = AerialDB(dataclasses.replace(cfg, planner="random"),
                            db.state, db.alive, jax.random.key(100 + r))
        full, _ = audit_db.query(catch_all)
        assert not bool(np.asarray(full.overflow)[0]), \
            "shard budget overflow — raise max_shards_per_query"
        completeness = int(full.count[0]) / total_expected
        print(f"round {r} [{phase:13s}] insert={(tq - t0) * 1e3:7.1f}ms "
              f"query(8)={(time.perf_counter() - tq) * 1e3:7.1f}ms "
              f"rows={np.asarray(result.count).mean():7.1f} "
              f"edges/query={np.asarray(qinfo.subquery_edges).mean():4.1f} "
              f"completeness={completeness:.4f}")

    assert completeness == 1.0, "<=2 failures must stay exact"
    print("mission complete: exact results under 2 edge failures")


if __name__ == "__main__":
    enable_compile_cache()
    main()
