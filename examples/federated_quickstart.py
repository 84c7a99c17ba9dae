"""Federated quickstart: the same AerialDB deployment on a 4-device edge mesh.

Each device of the ``("edge",)`` mesh plays two of the eight ground edge
servers. Both deployments are driven through the unified ``repro.api``
facade — ``AerialDB.open`` with a mesh shards the state and routes every
operation through shard_map; without one it runs the single-device jit path —
and, the point of the exercise, the results are identical.

    PYTHONPATH=src python examples/federated_quickstart.py

(The XLA flag below must be set before jax is imported: jax locks the host
device count at backend initialization.)
"""

import os

_FORCE = "--xla_force_host_platform_device_count"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FORCE}=4").strip()

import jax                    # noqa: E402
import numpy as np            # noqa: E402

from repro.api import AerialDB, Query, StoreConfig                       # noqa: E402
from repro.data.synthetic import CityConfig, DroneFleet, make_sites      # noqa: E402
from repro.launch.mesh import make_edge_mesh                             # noqa: E402
from repro.launch.compile_cache import enable_compile_cache              # noqa: E402


def main():
    n_edges, n_dev = 8, 4
    mesh = make_edge_mesh(n_dev)
    print(f"edge mesh: {n_dev} devices x {n_edges // n_dev} edges each "
          f"({jax.device_count()} host devices)")

    sites = make_sites(n_edges, CityConfig(), seed=3)
    cfg = StoreConfig(n_edges=n_edges, sites=tuple(map(tuple, sites.tolist())),
                      tuple_capacity=1 << 13, index_capacity=1024,
                      max_shards_per_query=64, records_per_shard=30)

    # --- one facade per runtime: the dispatch is the ONLY difference ---
    fed = AerialDB.open(cfg, mesh=mesh)
    ref = AerialDB.open(cfg)

    # --- ingest: 16 drones x 4 rounds, one fused lax.scan dispatch ---
    payloads, metas = DroneFleet(16, records_per_shard=30).next_rounds(4)
    fed.ingest_rounds(payloads, metas)
    ref.ingest_rounds(payloads, metas)
    per_edge = np.asarray(fed.state.tup_count)
    print(f"ingested {per_edge.sum()} tuple replicas across the mesh "
          f"(per-edge min={per_edge.min()} max={per_edge.max()})")

    # --- differential check: the same built queries, both runtimes ---
    queries = Query.batch(
        Query().bbox(12.90, 13.00, 77.50, 77.60).time(0.0, 300.0)
               .agg("count", "mean"),
        Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9)
               .agg("count", "mean"))
    key = jax.random.key(0)
    fed_res, fed_info = fed.query(queries, key=key)
    ref_res, _ = ref.query(queries, key=key)

    for i in range(2):
        print(f"query {i}: sharded count={int(fed_res.count[i])} "
              f"mean={float(fed_res.vmean[i]):.2f} "
              f"(single-device {int(ref_res.count[i])}), "
              f"edges_queried={int(fed_info.subquery_edges[i])}")
    np.testing.assert_array_equal(np.asarray(fed_res.count),
                                  np.asarray(ref_res.count))
    state_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(fed.state)))
    print(f"sharded == single-device: results exact, state identical="
          f"{state_equal}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
