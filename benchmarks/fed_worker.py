"""Subprocess worker for the fig7 sharded-runtime scaling sweep.

Runs a paper-scale deployment (default: 80 edges / 400 drones, §4.4.2 D400)
through the sharded federated runtime on N simulated host devices — on the
1-D ``("edge",)`` mesh, or with ``--fleets F`` on the 2-D ``("fleet",
"edge")`` mesh (hierarchical merge + double-buffered query tiling) — and
emits the usual ``name,us_per_call,derived`` rows on stdout. As a worker it
simulates N devices on the CPU: launch it with ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` already in the
environment (jax locks the device count at first backend initialization, so
the parent — fig7_insertion_scaling.py — sets both and spawns this module).
On a real accelerator fig7 calls :func:`sharded_rows` in its own process
instead, on the real device count: a chip belongs to one process.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.fed_worker --devices 4 --fleets 2

True cross-host mode — one OS process per fleet partition over
``jax.distributed`` (``launch.mesh.init_fleet_processes``); every process
runs the same command, ``--devices`` counts GLOBAL devices, and only process
0 prints rows:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \
      python -m benchmarks.fed_worker --devices 4 --fleets 2 \
      --coordinator localhost:9731 --num-processes 2 --process-id $RANK
"""

import argparse


def sharded_rows(devices: int, fleets: int = 1, edges: int = 80,
                 drones: int = 400, records: int = 15,
                 prefill_rounds: int = 2):
    """Insert timing and the exact catch-all query on a ``devices``-device
    mesh (``fleets`` > 1: the 2-D fleet mesh) in this process. Returns
    ``[(name, us_per_call, derived), ...]``. ``benchmarks.fig7`` calls it
    in-process on a real backend; ``main`` runs it in a worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import build_store, timeit
    from repro.core.datastore import make_pred
    from repro.core.placement import ShardMeta
    from repro.distributed.federation import (federated_insert_step,
                                              federated_query_step)
    from repro.launch.mesh import make_edge_mesh, make_fleet_mesh

    if jax.device_count() != devices:
        raise SystemExit(
            f"expected {devices} devices, found {jax.device_count()} — "
            "launch with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{devices}")
    if fleets > 1:
        mesh = make_fleet_mesh(fleets, devices // fleets, n_edges=edges)
    else:
        mesh = make_edge_mesh(devices, n_edges=edges)
    # tuple_capacity sized so the H_t hotspot edge (§3.4.1: one synchronous
    # round can land every shard's temporal replica on one edge) never wraps
    # within the run — keeps the catch-all count exact. min_edges planner:
    # its greedy loop is O(E) iterations vs O(#shards) for min_shards, which
    # matters at 1200 matched shards.
    cfg, state, alive, fleet, t_max, anchors = build_store(
        n_edges=edges, n_drones=drones, rounds=prefill_rounds,
        records=records, tuple_capacity=1 << 15, mesh=mesh,
        planner="min_edges",
        max_shards=2048)

    payload, meta = fleet.next_shards()
    meta = ShardMeta(*[jnp.asarray(x) for x in meta])
    pj = jnp.asarray(payload)
    us, (state2, _) = timeit(
        lambda: federated_insert_step(cfg, state, pj, meta, alive, mesh))
    tag = f"E{edges}/D{drones}/dev{devices}/fleet{fleets}"
    rows = [(f"fig7/sharded_insert/{tag}", us,
             f"us_per_shard={us / drones:.1f};devices={devices};"
             f"fleets={fleets}")]

    # Query smoke on the sharded store: exact catch-all count proves the
    # sharded runtime answered, not just ingested.
    pred = make_pred(q=1, t0=0.0, t1=1e9, has_temporal=True, is_and=True)
    result, _ = federated_query_step(cfg, state2, pred, alive,
                                     jax.random.key(0), mesh)
    expected = (prefill_rounds + 1) * drones * records
    got = int(np.asarray(result.count)[0])
    if got != expected:
        raise SystemExit(f"sharded catch-all count {got} != {expected}")
    rows.append((f"fig7/sharded_query_exact/{tag}", 0.0,
                 f"count={got};fleets={fleets}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True,
                    help="total (global) device count the mesh must span")
    ap.add_argument("--fleets", type=int, default=1,
                    help="fleet partitions: 1 = 1-D ('edge',) mesh, "
                         ">1 = 2-D ('fleet', 'edge') mesh")
    ap.add_argument("--edges", type=int, default=80)
    ap.add_argument("--drones", type=int, default=400)
    ap.add_argument("--records", type=int, default=15)
    ap.add_argument("--prefill-rounds", type=int, default=2)
    ap.add_argument("--coordinator", default=None,
                    help="host:port — run multi-process over jax.distributed "
                         "(one process per fleet partition)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()

    if args.coordinator is not None:
        # Must run before any other jax API touches the backend.
        from repro.launch.mesh import init_fleet_processes
        init_fleet_processes(args.coordinator, args.num_processes,
                             args.process_id)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = sharded_rows(args.devices, args.fleets, args.edges, args.drones,
                        args.records, args.prefill_rounds)
    if jax.process_index() == 0:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()
