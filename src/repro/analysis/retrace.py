"""aeriallint layer 2: the jit-retrace budget harness.

Every federated operation dispatches through an ``lru_cache``-memoized jitted
entry point (``distributed.federation._insert_fn`` / ``_ingest_fn`` /
``_query_fn``; single-device ``core.datastore._insert_step_jit`` /
``_query_step_jit``). The steady-state contract is *zero retraces*: a fleet
session compiles each entry point once per (config, mesh, AggSpec-channels)
key and then never again — a weak-hash config dataclass, a shape-unstable
call site, or an accidentally-traced Python value silently 10x's ingest
latency without failing any correctness test.

This harness runs the canonical facade workload (insert, fused multi-round
ingest, one query per AggSpec channel set, fail/recover with implicit
repair, then post-repair re-insert/re-query) on every configured mesh shape
plus the single-device path, under a compilation counter, and asserts

  * **cold**: each budgeted entry point compiles EXACTLY its
    ``[tool.aeriallint.retrace.budgets]`` count, and
  * **warm**: a second, fresh session over the same config re-runs the whole
    workload and compiles none of them (the caches are keyed by value-equal
    configs, so a fresh ``AerialDB.open`` must be a pure cache hit).

Counting uses ``jax_log_compiles``: XLA's dispatch layer logs
``"Compiling <name> with global shapes ..."`` exactly once per jit cache
miss (the persistent compilation cache short-circuits *compilation*, not the
trace, so counts stay deterministic under a warm ``.jax_cache``).

CLI (also a tier-1 test — ``tests/test_analysis.py``):

    python -m repro.analysis.retrace            # human-readable, exit 1 on violation
    python -m repro.analysis.retrace --json -o ANALYSIS_retrace.json
"""

import os

# The canonical meshes need 4 host devices; the flag only matters before the
# first backend use, so setting it at import is safe even when a test runner
# (tests/conftest.py) already configured it.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import argparse
import collections
import json
import logging
import re
import sys
from typing import Optional

import jax

from repro.analysis.config import AeriallintConfig, load_config
from repro.api import AerialDB, AggSpec, Query, StoreConfig
from repro.data.synthetic import DroneFleet
from repro.launch.mesh import make_edge_mesh, make_fleet_mesh

# "Compiling jit(<name>) with global shapes and types ..." — emitted by the
# dispatch/pxla layer once per jit cache miss when jax_log_compiles is on.
# The captured group is the bare entry-point name the budget table keys on.
_COMPILE_RE = re.compile(r"Compiling jit\(([^\s()]+)\) with global shapes")
_JAX_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class CompileCounter(logging.Handler):
    """Context manager counting XLA compilations by jitted-function name.

    Usage::

        with CompileCounter() as cc:
            run_workload()
        assert cc.counts["fed_query"] == 2

    ``counts`` maps jaxpr entry-point name -> number of compilations
    observed inside the ``with`` block (a ``collections.Counter``).
    """

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.counts = collections.Counter()

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.counts[m.group(1)] += 1

    def __enter__(self):
        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._prev_levels = []
        for name in _JAX_LOGGERS:
            lg = logging.getLogger(name)
            self._prev_levels.append((lg, lg.level))
            lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        for lg, _lvl in self._prev_levels:
            lg.removeHandler(self)
        jax.config.update("jax_log_compiles", self._prev)
        return False


# Distinctive shapes so the harness' jit cache keys cannot collide with any
# other config in the process (tier-1 runs this in the same interpreter as
# the rest of the suite; a shared (cfg, mesh) key would eat a cold compile).
_CANON_KWARGS = dict(n_edges=8, tuple_capacity=384, index_capacity=160,
                     max_shards_per_query=24, records_per_shard=3, n_values=2)
_N_DRONES = 6


def canonical_config(**overrides) -> StoreConfig:
    kw = dict(_CANON_KWARGS)
    kw.update(overrides)
    return StoreConfig(**kw)


def mesh_for(shape, n_edges: int):
    """Build the datastore mesh for a budget mesh shape: (N,) -> 1-D edge
    mesh, (F, E) -> 2-D (fleet, edge) mesh."""
    shape = tuple(int(x) for x in shape)
    if len(shape) == 1:
        return make_edge_mesh(shape[0], n_edges=n_edges)
    if len(shape) == 2:
        return make_fleet_mesh(shape[0], shape[1], n_edges=n_edges)
    raise ValueError(f"unsupported retrace mesh shape {shape}: the runtime "
                     "has 1-D (edge,) and 2-D (fleet, edge) meshes.")


def canonical_workload(cfg: StoreConfig, mesh) -> None:
    """The facade workload every budget is defined against: one insert, one
    fused 2-round ingest, one query per AggSpec channel set, a fail/recover
    cycle (implicit incremental repair), then a post-repair re-insert and
    re-query — the latter two must be pure cache hits even cold."""
    db = AerialDB.open(cfg, mesh=mesh, seed=0)
    fleet = DroneFleet(_N_DRONES, records_per_shard=cfg.records_per_shard,
                       n_values=cfg.n_values, seed=7)
    db.insert(*fleet.next_shards())
    db.ingest_rounds(*fleet.next_rounds(2))

    window = Query().bbox(12.0, 14.0, 77.0, 79.0).time(0.0, 1e5)
    single = window.agg("mean", channel=0)
    db.query(single)
    pred, _ = window.build()
    db.query(pred, agg=AggSpec(channels=(0, 1)))

    db.fail_edges(1)
    db.query(single)                      # re-plan around the dead edge
    db.recover_edges(1)                   # implicit incremental repair
    db.insert(*fleet.next_shards())       # post-repair: zero retraces
    db.query(pred, agg=AggSpec(channels=(0, 1)))


def _check(budgets: dict, counts: collections.Counter, phase: str,
           label: str) -> list:
    out = []
    for name, want in budgets.items():
        want = want if phase == "cold" else 0
        got = counts.get(name, 0)
        if got != want:
            out.append({
                "mesh": label, "phase": phase, "entry": name,
                "want": want, "got": got,
                "message": (f"[{label}/{phase}] jitted entry '{name}' "
                            f"compiled {got}x, budget is {want} — "
                            + ("a retrace regression (weak config hash / "
                               "shape-unstable call site?)" if phase == "warm"
                               or got > want else
                               "either dead dispatch or a stale budget "
                               "table in [tool.aeriallint.retrace]")),
            })
    return out


def run_retrace(repo_root: Optional[str] = None,
                cfg: Optional[AeriallintConfig] = None,
                seed_offset: int = 0) -> dict:
    """Run the budget harness on every configured mesh shape plus the
    single-device path; returns the machine-readable report.

    ``seed_offset`` perturbs the canonical StoreConfig's capacities so a
    repeated in-process run (e.g. CLI after the test suite already ran the
    harness) still measures a cold cache.
    """
    cfg = cfg or load_config(repo_root)
    extra = {"tuple_capacity": 384 + 128 * seed_offset} if seed_offset else {}
    store_cfg = canonical_config(**extra)

    runs = []
    legs = [("single_device", None, cfg.budgets(federated=False))]
    if jax.device_count() >= 4:
        for shape in cfg.retrace_mesh_shapes:
            label = "mesh" + str(tuple(int(x) for x in shape))
            legs.append((label, mesh_for(shape, store_cfg.n_edges),
                         cfg.budgets(federated=True)))
    else:  # pragma: no cover - CI always forces 4 host devices
        runs.append({"mesh": "mesh-legs-skipped",
                     "reason": f"device_count={jax.device_count()} < 4"})

    violations = []
    for label, mesh, budgets in legs:
        with CompileCounter() as cold:
            canonical_workload(store_cfg, mesh)
        with CompileCounter() as warm:
            canonical_workload(store_cfg, mesh)   # fresh session, same keys
        v = (_check(budgets, cold.counts, "cold", label)
             + _check(budgets, warm.counts, "warm", label))
        violations += v
        runs.append({"mesh": label, "budgets": budgets,
                     "cold": dict(cold.counts), "warm": dict(warm.counts),
                     "violations": len(v)})
    return {
        "tool": "aeriallint.retrace",
        "mesh_shapes": [list(s) for s in cfg.retrace_mesh_shapes],
        "runs": runs,
        "violations": violations,
        "ok": not violations,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.retrace",
        description="aeriallint layer 2: jit-retrace budget harness.")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report")
    ap.add_argument("-o", "--output", default=None,
                    help="also write the JSON report to this file")
    ap.add_argument("--root", default=None, help="repo root override")
    args = ap.parse_args(argv)

    report = run_retrace(args.root)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
    if args.json:
        json.dump(report, sys.stdout, indent=2)
        print()
    else:
        for v in report["violations"]:
            print(v["message"])
        n_legs = sum("budgets" in r for r in report["runs"])
        print(f"aeriallint.retrace: {n_legs} leg(s), "
              f"{len(report['violations'])} budget violation(s).")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
