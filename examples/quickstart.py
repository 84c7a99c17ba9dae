"""Quickstart: stand up an AerialDB deployment, ingest a drone fleet, query
it — all through the unified ``repro.api`` facade.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.api import AerialDB, Query
from repro.data.synthetic import CityConfig, DroneFleet, make_sites
from repro.launch.compile_cache import enable_compile_cache


def main():
    # --- deployment: 12 edge servers over the city (paper §3.3) ---
    n_edges = 12
    sites = make_sites(n_edges, CityConfig(), seed=3)
    db = AerialDB.open(n_edges=n_edges,
                       sites=tuple(map(tuple, sites.tolist())),
                       tuple_capacity=1 << 14, index_capacity=2048,
                       max_shards_per_query=64, records_per_shard=30)

    # --- ingest: 16 drones x 4 collection rounds, one fused dispatch ---
    fleet = DroneFleet(16, records_per_shard=30)
    payloads, metas = fleet.next_rounds(4)
    db.ingest_rounds(payloads, metas)
    per_edge = np.asarray(db.state.tup_count)
    print(f"ingested {per_edge.sum()} tuple replicas "
          f"(balance: min={per_edge.min()} max={per_edge.max()})")

    # --- query: spatio-temporal AND predicates, one compiled batch ---
    pred, spec = Query.batch(
        Query().bbox(12.90, 13.00, 77.50, 77.60).time(0.0, 300.0)
               .agg("count", "mean"),
        Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9)
               .agg("count", "mean"))
    result, info = db.query((pred, spec))
    for i in range(2):
        print(f"query {i}: count={int(result.count[i])} "
              f"mean_v={float(result.vmean[i]):.2f} "
              f"edges_queried={int(info.subquery_edges[i])}")

    # --- resilience: kill two edges, same query, exact answer (§3.5.3) ---
    db.fail_edges(2, 7)
    result2, _ = db.query((pred, spec))
    assert int(result2.count[1]) == int(result.count[1]), "lost data!"
    db.recover_edges(2, 7)
    print("2 edges down -> identical results (3-replica guarantee holds)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
