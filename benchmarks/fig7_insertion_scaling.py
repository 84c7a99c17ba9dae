"""Fig 7 + §4.4.2: insertion latency D100 (20 edges) vs D400 (80 edges), the
replica load-balance band across edges, and sharded-runtime insertion scaling
of the paper-scale D400 config. On the CPU backend the scaling sweep is a
simulation over 1/2/4/8 virtual devices — each worker subprocess pins
``JAX_PLATFORMS=cpu`` and forces its own host device count, since jax locks
it at backend initialization. On an accelerator the sweep runs in this
process on the real device count: a chip belongs to one process, so no
child may need it.

Balance note: the paper's §3.4.1 discusses the temporal-clustering hotspot —
when every drone emits a shard with the SAME collection timestamp, H_t sends
one replica of each to the same edge. A single synchronous round reproduces
that hotspot here (visible as max >> mean); with multiple rounds (temporal
diversity, as in the paper's 48 h workload) the band tightens toward the
paper's 3846-4479 range.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_store, emit, timed_insert, timeit
from benchmarks.fed_worker import sharded_rows
from repro.core.placement import ShardMeta

REPO_ROOT = Path(__file__).resolve().parent.parent


# (devices, fleets) sweep: 1-D mesh scaling over 1/2/4/8 devices, plus the
# 2-D ("fleet", "edge") mesh at 1/2/4 fleet partitions on 4 devices — the
# 1/2/4-fleet scaling rows of BENCH_fig7_insertion_scaling.json. Override
# with FIG7_SWEEP="dev:fleet,dev:fleet,..." (CI runs a light subset).
DEFAULT_SWEEP = ((1, 1), (2, 1), (4, 1), (8, 1), (4, 2), (4, 4))


def _sweep():
    spec = os.environ.get("FIG7_SWEEP")
    if not spec:
        return DEFAULT_SWEEP
    return tuple(tuple(int(x) for x in pair.split(":"))
                 for pair in spec.split(","))


def run_sharded_scaling(sweep=None):
    """Paper-scale 80-edge/400-drone ingest through the sharded federated
    runtime, one CPU-simulation subprocess per (device count, fleet count)
    mesh shape."""
    for ndev, nfleet in (_sweep() if sweep is None else sweep):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        src = str(REPO_ROOT / "src")
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.fed_worker",
             "--devices", str(ndev), "--fleets", str(nfleet)],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fed_worker (devices={ndev}, fleets={nfleet}) failed:\n"
                f"{proc.stderr[-4000:]}")
        for line in proc.stdout.splitlines():
            if line.startswith("fig7/"):
                name, us, derived = line.split(",", 2)
                emit(name, float(us), derived)


def run_sharded_in_process():
    """The same rows on the accelerator's real devices, in this process:
    the 1-D mesh over every device, and the 2-D mesh at two fleets."""
    n = jax.device_count()
    for nfleet in (1, 2):
        if n % nfleet == 0:
            for row in sharded_rows(n, nfleet):
                emit(*row)


def run():
    if jax.default_backend() == "cpu":
        # Simulated devices: the children are CPU-only, started before this
        # process compiles anything.
        run_sharded_scaling()
    for name, n_edges, n_drones in [("D100", 20, 100), ("D400", 80, 400)]:
        cfg, state, alive, fleet, _, _ = build_store(
            n_edges=n_edges, n_drones=n_drones, rounds=6, records=15,
            tuple_capacity=1 << 16)
        payload, meta = fleet.next_shards()
        meta = ShardMeta(*[jnp.asarray(x) for x in meta])
        pj = jnp.asarray(payload)
        us, state2 = timeit(
            lambda: timed_insert(cfg, state, alive, pj, meta))
        emit(f"fig7/insert/{name}", us,
             f"us_per_shard={us/n_drones:.1f};drones={n_drones};edges={n_edges}")
        per_edge = np.asarray(state2.tup_count) // cfg.records_per_shard
        emit(f"fig7/replica_balance/{name}", 0.0,
             f"replicas_per_edge_min={per_edge.min()};max={per_edge.max()};"
             f"mean={per_edge.mean():.0f}")
        # single synchronous round: the paper's discussed H_t hotspot
        cfg1, state1, alive1, fleet1, _, _ = build_store(
            n_edges=n_edges, n_drones=n_drones, rounds=1, records=15)
        pe1 = np.asarray(state1.tup_count) // cfg1.records_per_shard
        emit(f"fig7/hotspot_single_round/{name}", 0.0,
             f"max={pe1.max()};mean={pe1.mean():.0f};"
             f"paper_s3.4.1_temporal_clustering")

    if jax.default_backend() != "cpu":
        run_sharded_in_process()
