"""On-chip smoke run of AerialDB's served path at the paper's D400 scale.

Drives the ``AerialDB`` facade and ``IngestPipeline`` once, end to end, on
the TPU, at the deployment of the paper's §4.4.2 evaluation: 80 edges, 400
drones, 3 replicas, 60-sample shards every five minutes, the nine §4.5.1
query windows, and rings sized for a 12 h mission (``tuple_capacity =
1 << 17`` slots per edge, about 0.4 GB of state on the device). Data comes
from ``DroneFleet`` and ``--seed``. Every answer is checked against a plain
numpy reference over the generated payloads; any failed check or phase
exits nonzero.

    python chip_smoke.py              # one chip: single-device session
    python chip_smoke.py --chips 4    # four chips: the (4,) and (2, 2)
                                      # meshes only, 20 edges per chip

Earlier lines are one JSON object per phase (wall times with compile kept
apart from a warm second call, bytes, overflow counts). They are first
readings of a smoke run, not benchmark numbers. The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script refuses to run without a TPU: JAX falls back to the CPU quietly
when the TPU fails to initialise, and that must fail here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import paper_workloads  # noqa: E402
from repro.api import AerialDB, AggSpec  # noqa: E402
from repro.core import datastore as _ds  # noqa: E402
from repro.core.datastore import StoreConfig  # noqa: E402
from repro.data.synthetic import CityConfig, DroneFleet, make_sites  # noqa: E402
from repro.distributed import federation as _fed  # noqa: E402
from repro.ingest import IngestPipeline, latest_oracle  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_edge_mesh, make_fleet_mesh  # noqa: E402

# Every query aggregates all four sensor channels in one fused scan.
AGG = AggSpec(channels=(0, 1, 2, 3))
# float32 sums over up to ~10^4 values of ~25 differ from the float64
# reference by accumulation order only; min/max select stored values and
# must match exactly.
SUM_RTOL = 1e-4
# The Pallas engine as the chip runs it: compiled, never interpreted.
KERNEL = {"use_kernel": True, "interpret": False}
# Shards per device batch. The pipeline's default (256 at this ring size)
# makes an insert program that took 133 s to compile for a described v5e on
# the CPU host, against 8 s at 32; fused ingest runs amortise the dispatches.
BATCH_SHARDS = 32


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The smoke deployment; the defaults are D400 (paper §4.4.2)."""
    n_edges: int = 80
    n_drones: int = 400
    replication: int = 3
    records_per_shard: int = 60
    n_values: int = 4
    max_shards_per_query: int = 512
    tuple_capacity: int = 1 << 17     # ~12 h of D400 at 3 replicas per edge
    preload_rounds: int = 24          # 2 h: two flushes of 12 rounds
    stream_rounds: int = 1
    queries_per_window: int = 8

    def config(self, seed: int, n_failure_domains: int = 1) -> StoreConfig:
        sites = make_sites(self.n_edges, CityConfig(), seed=seed)
        return StoreConfig(
            n_edges=self.n_edges, sites=tuple(map(tuple, sites.tolist())),
            replication=self.replication,
            records_per_shard=self.records_per_shard,
            n_values=self.n_values,
            max_shards_per_query=self.max_shards_per_query,
            tuple_capacity=self.tuple_capacity, max_drones=self.n_drones,
            n_failure_domains=n_failure_domains)


D400 = Deployment()


def log(**fields) -> None:
    print(json.dumps(fields, default=lambda o: o.item()), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class Data:
    """The fleet's telemetry for the whole run, generated once from the
    seed: records in stream order plus the per-round split the loader
    submits."""

    def __init__(self, dep: Deployment, seed: int):
        n = dep.preload_rounds + dep.stream_rounds
        fleet = DroneFleet(dep.n_drones, records_per_shard=dep.records_per_shard,
                           n_values=dep.n_values, seed=seed)
        payloads, _ = fleet.next_rounds(n)              # (N, D, R, W)
        n_, d, r, w = payloads.shape
        self.rows = payloads.reshape(-1, w)
        self.drone = np.broadcast_to(np.arange(d)[None, :, None],
                                     (n_, d, r)).reshape(-1)
        self.seq = (np.arange(n_)[:, None, None] * r
                    + np.arange(r)[None, None, :]).repeat(d, 1).reshape(-1)
        self.per_round = d * r
        half = dep.preload_rounds // 2
        self.steps = (("preload_cold", 0, half),
                      ("preload_warm", half, dep.preload_rounds),
                      ("stream", dep.preload_rounds, n))
        self.windows = paper_workloads(float(self.rows[:, 0].max()),
                                       n_queries=dep.queries_per_window,
                                       seed=seed + 11,
                                       anchors=self.rows[:, :3])


def numpy_reference(rows: np.ndarray, pred) -> dict:
    """Plain numpy answer of an AND spatio-temporal window batch over every
    generated record: count, and float64 sum / float32 min / max of each
    channel (NaN where nothing matches)."""
    p = {f: np.asarray(getattr(pred, f))[:, None] for f in
         ("lat0", "lat1", "lon0", "lon1", "t0", "t1")}
    t, lat, lon = rows[None, :, 0], rows[None, :, 1], rows[None, :, 2]
    m = ((p["lat0"] <= lat) & (lat <= p["lat1"]) & (p["lon0"] <= lon)
         & (lon <= p["lon1"]) & (p["t0"] <= t) & (t <= p["t1"]))  # (Q, N)
    vals = rows[:, [3 + c for c in AGG.channels]]                   # (N, K)
    out = {"count": m.sum(1), "sum": m.astype(np.float64) @ vals,
           "min": np.full((m.shape[0], vals.shape[1]), np.nan, np.float32),
           "max": np.full((m.shape[0], vals.shape[1]), np.nan, np.float32)}
    for i in np.nonzero(out["count"])[0]:
        out["min"][i] = vals[m[i]].min(0)
        out["max"][i] = vals[m[i]].max(0)
    return out


def state_bytes(db: AerialDB) -> int:
    return int(sum(x.nbytes for x in jax.tree.leaves(db.state)))


def peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


# -- phases ------------------------------------------------------------------

def load(db: AerialDB, data: Data, tag: str) -> IngestPipeline:
    """Preload 2 h then stream the live round(s), all through the ingest
    pipeline, whose flush drives ``AerialDB.ingest_rounds`` in fused
    full-batch runs and ``AerialDB.insert`` for the tail."""
    pipe = IngestPipeline(db, batch_shards=BATCH_SHARDS)
    for name, r0, r1 in data.steps:
        sl = slice(r0 * data.per_round, r1 * data.per_round)
        rows = data.rows[sl]
        t0 = time.perf_counter()
        pipe.submit_arrays(data.drone[sl], data.seq[sl], rows[:, 0],
                           rows[:, 1], rows[:, 2], rows[:, 3:])
        out = pipe.flush()
        log(phase=f"{tag}/ingest/{name}", rounds=r1 - r0,
            shards=out["flushed_shards"], dispatches=out["dispatches"],
            seconds=time.perf_counter() - t0)
        check(out["flushed_records"] == rows.shape[0] and pipe.pending == 0,
              f"{tag}/{name}: every submitted record flushed")
    rec = pipe.reconcile()
    dropped = int(np.asarray(db.state.index.dropped).sum())
    wrapped = int(np.asarray(db.state.tup_overwritten).sum())
    log(phase=f"{tag}/reconcile", ok=rec["ok"],
        stored_tuples=rec["stored_tuples"],
        index_entries_dropped=dropped, tuples_overwritten=wrapped,
        state_bytes=state_bytes(db), peak_bytes_in_use=peak_bytes())
    check(rec["ok"], f"{tag}: reconcile() {rec}")
    check(dropped == 0, f"{tag}: no round dropped index entries")
    check(wrapped == 0, f"{tag}: no ring wrapped (exact-answer regime)")
    return pipe


def engines(db: AerialDB) -> dict:
    """The served default (jnp reference scan) and the compiled Pallas
    kernel, both over the session's current state and membership."""
    kernel = AerialDB(db.cfg, db.state, db.effective_alive,
                      jax.random.key(0), mesh=db.mesh, **KERNEL)
    return {"jnp": db, "pallas": kernel}


def run_queries(db: AerialDB, data: Data, refs: dict, tag: str,
                warm: bool = False) -> dict:
    """The nine windows on both engines. Counts must be identical between
    the engines and equal the numpy reference wherever the shard budget did
    not overflow; sums agree to SUM_RTOL, min/max exactly. Returns
    {window: (count, overflow, vsum)} of the jnp engine."""
    out = {}
    for wi, (name, pred) in enumerate(data.windows.items()):
        got, row = {}, {"phase": f"{tag}/query/{name}"}
        for eng, session in engines(db).items():
            key = jax.random.key(wi)
            (res, _), sec = timed(session.query, pred, agg=AGG, key=key)
            row[f"{eng}_s"] = sec
            if warm:
                _, row[f"{eng}_warm_s"] = timed(session.query, pred, agg=AGG,
                                                key=key)
            got[eng] = res
        a, b = got["jnp"], got["pallas"]
        ovf = np.asarray(a.overflow)
        check(np.array_equal(np.asarray(a.count), np.asarray(b.count)),
              f"{tag}/{name}: engine counts identical")
        check(np.array_equal(ovf, np.asarray(b.overflow)),
              f"{tag}/{name}: engine overflow identical")
        ref, ok = refs[name], ~ovf
        for res in (a, b):
            check(np.array_equal(np.asarray(res.count)[ok], ref["count"][ok]),
                  f"{tag}/{name}: counts equal the numpy reference")
            check(np.allclose(np.asarray(res.vsum)[ok], ref["sum"][ok],
                              rtol=SUM_RTOL),
                  f"{tag}/{name}: sums within {SUM_RTOL}")
            for agg in ("min", "max"):
                check(np.array_equal(np.asarray(getattr(res, f"v{agg}"))[ok],
                                     ref[agg][ok], equal_nan=True),
                      f"{tag}/{name}: {agg} equal the numpy reference")
        row.update(queries=int(ovf.size), overflow=int(ovf.sum()),
                   matched_tuples=int(ref["count"].sum()))
        log(**row)
        out[name] = (np.asarray(a.count), ovf, np.asarray(a.vsum))
    short = [n for n in out if not n.startswith("2h")]
    check(len(short) == 6 and not any(out[n][1].any() for n in short),
          f"{tag}: the six 5-min and 30-min windows are compared in full")
    return out


def check_same(base: dict, now: dict, what: str) -> None:
    for name, (count, ovf, vsum) in base.items():
        check(np.array_equal(count, now[name][0])
              and np.array_equal(ovf, now[name][1]),
              f"{what}: counts unchanged in {name}")
        check(np.allclose(vsum, now[name][2], rtol=SUM_RTOL, equal_nan=True),
              f"{what}: sums unchanged in {name}")


def check_kernel_hlo(db: AerialDB, pred) -> None:
    """The compiled kernel-engine query program contains the Mosaic call.
    The facade has no lowering surface, so this lowers the same jitted
    function the facade dispatches to."""
    key = jax.random.key(0)
    t0 = time.perf_counter()
    if db.mesh is None:
        lowered = _ds._query_step_jit.lower(
            db.cfg, db.state, pred, db.effective_alive, key,
            KERNEL["use_kernel"], KERNEL["interpret"], AGG.channels)
    else:
        lowered = _fed._query_fn(
            db.cfg, db.mesh, KERNEL["use_kernel"], KERNEL["interpret"],
            AGG.channels).lower(db.state, pred, db.effective_alive,
                                jax.random.key_data(key))
    text = lowered.compile().as_text()
    log(phase="kernel_hlo", tpu_custom_call="tpu_custom_call" in text,
        seconds=time.perf_counter() - t0)
    check("tpu_custom_call" in text, "kernel query HLO has tpu_custom_call")


def check_latest(db: AerialDB, data: Data, tag: str) -> None:
    res, sec = timed(db.latest)
    want, valid = latest_oracle(data.drone, data.rows[:, 0], data.rows,
                                db.cfg.max_drones)
    ok = (np.array_equal(np.asarray(res.valid), valid)
          and np.array_equal(np.asarray(res.record), want))
    log(phase=f"{tag}/latest", exact=ok, seconds=sec)
    check(ok, f"{tag}: latest() equals latest_oracle")


def run_one_chip(dep: Deployment, seed: int) -> None:
    data = Data(dep, seed)
    refs = {n: numpy_reference(data.rows, p) for n, p in data.windows.items()}
    t0 = time.perf_counter()
    db = AerialDB.open(dep.config(seed), seed=seed)
    log(phase="open", seconds=time.perf_counter() - t0,
        state_bytes=state_bytes(db))
    pipe = load(db, data, "1chip")
    healthy = run_queries(db, data, refs, "1chip/healthy", warm=True)
    check_kernel_hlo(db, next(iter(data.windows.values())))
    check_latest(db, data, "1chip")

    counts = np.asarray(db.state.tup_count)
    dead = sorted(int(e) for e in np.argsort(counts)[-2:])
    db.fail_edges(*dead)
    log(phase="1chip/fail_edges", edges=dead)
    check_same(healthy, run_queries(db, data, refs, "1chip/failed"),
               "1chip: two dead edges")
    t0 = time.perf_counter()
    db.recover_edges(*dead)
    jax.block_until_ready(db.state)
    log(phase="1chip/recover_edges", seconds=time.perf_counter() - t0,
        repair={k: v for k, v in db.last_repair.items()
                if isinstance(v, (int, float, str))})
    check_same(healthy, run_queries(db, data, refs, "1chip/recovered"),
               "1chip: after recovery and repair")
    check(pipe.reconcile()["ok"], "1chip: reconcile() after repair")
    log(phase="1chip/end", peak_bytes_in_use=peak_bytes())


def run_four_chips(dep: Deployment, seed: int) -> None:
    data = Data(dep, seed)
    refs = {n: numpy_reference(data.rows, p) for n, p in data.windows.items()}
    cfg = dep.config(seed, n_failure_domains=4)
    results = {}
    for tag, mesh in (("edge4", make_edge_mesh(4, n_edges=dep.n_edges)),
                      ("fleet2x2", make_fleet_mesh(2, 2, n_edges=dep.n_edges))):
        db = AerialDB.open(cfg, mesh=mesh, seed=seed)
        shards = db.state.tup_f.addressable_shards
        devices = {s.device.id for s in shards}
        log(phase=f"{tag}/placement", devices=sorted(devices),
            shard_shape=list(shards[0].data.shape))
        check(len(shards) == 4 and len(devices) == 4,
              f"{tag}: every shard of tup_f on its own device")
        load(db, data, tag)
        healthy = run_queries(db, data, refs, f"{tag}/healthy", warm=True)
        check_kernel_hlo(db, next(iter(data.windows.values())))
        check_latest(db, data, tag)
        db.fail_device(1)
        log(phase=f"{tag}/fail_device", device=1)
        check_same(healthy, run_queries(db, data, refs, f"{tag}/failed"),
                   f"{tag}: device 1 down")
        results[tag] = healthy
        log(phase=f"{tag}/end", peak_bytes_in_use=peak_bytes())
    check_same(results["edge4"], results["fleet2x2"], "(4,) vs (2, 2) mesh")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded (4,) and (2, 2) mesh path")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); this run needs the chip.")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}.")
    log(phase="device", kind=dev.device_kind, count=len(devices),
        compile_cache=cache, jax=jax.__version__)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(D400, args.seed)
    else:
        run_four_chips(D400, args.seed)
    log(phase="total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
