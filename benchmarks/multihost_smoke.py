"""2-process ``jax.distributed`` CPU smoke: the cross-host fleet runtime.

The real multi-process path the simulated fleet mesh stands in for: a local
coordinator plus 2 worker processes x 2 simulated host devices each, one
process per fleet partition of a ``(2, 2) ("fleet", "edge")`` mesh
(``launch.mesh.init_fleet_processes`` selects the gloo CPU collectives
transport). Each worker drives the federation differential harness
end-to-end — fused ingest, inserts during an edge outage, queries before /
during / after failures — against a process-local single-device reference,
comparing replicated query results exactly and each process's addressable
state shards against the reference slice (the cross-process state is never
gathered: every process checks exactly the edge blocks it hosts).

Parent mode (no args) spawns the workers and gates on both exiting clean:

    PYTHONPATH=src python -m benchmarks.multihost_smoke

Used by CI as the multihost leg; also a how-to template for running
``benchmarks/fed_worker.py`` with --coordinator/--num-processes/--process-id.
"""

import argparse
import os
import socket
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

N_PROC = 2
DEV_PER_PROC = 2
E = 8
ROUNDS = 3


def child(coordinator: str, process_id: int) -> None:
    from repro.launch.mesh import init_fleet_processes, make_fleet_mesh
    init_fleet_processes(coordinator, N_PROC, process_id)

    import jax
    import jax.numpy as jnp
    import numpy as np

    assert jax.process_count() == N_PROC
    assert jax.local_device_count() == DEV_PER_PROC
    assert jax.device_count() == N_PROC * DEV_PER_PROC
    mesh = make_fleet_mesh(N_PROC, DEV_PER_PROC, n_edges=E)

    from repro.api import AerialDB, Query
    from repro.core.placement import ShardMeta
    from repro.data.synthetic import CityConfig, DroneFleet, make_sites
    from repro.core.datastore import StoreConfig

    sites = make_sites(E, CityConfig(), seed=3)
    cfg = StoreConfig(
        n_edges=E, sites=tuple(map(tuple, sites.tolist())),
        tuple_capacity=2048, index_capacity=512, max_shards_per_query=64,
        records_per_shard=12, retention_every=2)
    db_ref = AerialDB.open(cfg)             # process-local single device
    db_fed = AerialDB.open(cfg, mesh=mesh)  # global (2, 2) fleet mesh

    def check_states(what):
        """Every leaf of the sharded state, checked shard-by-shard against
        the local reference — each process validates the blocks it hosts."""
        for name, ref, fed in zip(
                [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(db_ref.state)[0]],
                jax.tree.leaves(db_ref.state), jax.tree.leaves(db_fed.state)):
            ref = np.asarray(ref)
            for s in fed.addressable_shards:
                np.testing.assert_array_equal(
                    np.asarray(s.data), ref[s.index],
                    err_msg=f"{what}: {name} shard {s.index}")

    def check_query(what, q, key):
        r1, i1 = db_ref.query(q, key=key)
        r2, i2 = db_fed.query(q, key=key)
        for f in r1._fields:
            a, b = np.asarray(getattr(r1, f)), np.asarray(getattr(r2, f))
            if f in ("vsum", "vmean"):  # cross-device accumulation order
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                           err_msg=f"{what}: {f}")
            else:
                np.testing.assert_array_equal(a, b, err_msg=f"{what}: {f}")
        for f in i1._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(i1, f)), np.asarray(getattr(i2, f)),
                err_msg=f"{what}: {f}")

    fleet = DroneFleet(10, records_per_shard=12, seed=43)
    pay, met = fleet.next_rounds(ROUNDS)
    db_ref.ingest_rounds(pay, met)
    db_fed.ingest_rounds(pay, met)
    check_states("post-ingest")

    q = Query().time(0.0, 1e9).agg("count", "mean", channel=1)
    qbox = (Query().bbox(12.85, 13.10, 77.45, 77.75)
            & Query().time(0.0, 1e9)).agg("count", "min", "max", channel=2)
    check_query("healthy", q, jax.random.key(7))
    check_query("healthy-bbox", qbox, jax.random.key(9))

    db_ref.fail_edges(1, 5)
    db_fed.fail_edges(1, 5)
    check_query("degraded", q, jax.random.key(11))
    p, m = DroneFleet(6, records_per_shard=12, seed=8).next_shards()
    m = ShardMeta(*[jnp.asarray(x) for x in m])
    db_ref.insert(p, m)
    db_fed.insert(p, m)
    # repair=False: the anti-entropy pass is host-side control-plane work
    # that gathers the full state — process-local by design, exercised on
    # the simulated (single-process) fleet mesh in tests/test_federation.py.
    db_ref.recover_edges(1, 5, repair=False)
    db_fed.recover_edges(1, 5, repair=False)
    check_states("post-recovery")
    check_query("recovered", q, jax.random.key(13))

    print(f"multihost_smoke: process {process_id} OK "
          f"({jax.process_count()} processes x {DEV_PER_PROC} devices, "
          f"mesh {dict(mesh.shape)})", flush=True)


def parent() -> None:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coordinator = f"localhost:{port}"

    env = dict(os.environ)
    # A simulation of several hosts on the CPU: the workers never take an
    # accelerator (a chip belongs to one process).
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEV_PER_PROC}")
    src = str(REPO_ROOT / "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "benchmarks.multihost_smoke", "--child",
             "--coordinator", coordinator, "--process-id", str(i)],
            env=env, cwd=REPO_ROOT)
        for i in range(N_PROC)]
    codes = [p.wait() for p in procs]
    if any(codes):
        raise SystemExit(f"multihost smoke failed: worker exit codes {codes}")
    print(f"multihost_smoke: OK ({N_PROC} processes, coordinator "
          f"{coordinator})", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()
    if args.child:
        child(args.coordinator, args.process_id)
    else:
        parent()


if __name__ == "__main__":
    main()
