"""One run of one cell: set-up, the serving loop's window, the check.

Everything is found by name: the cell in ``bench/cells/<cell>.json`` names
its configuration (``bench/configs/``) and traffic mix
(``bench/traffic/``); the mix names its generator (``bench/gen/<kind>.py``);
each metric listed for the cell in ``BENCHMARK.json`` is read by
``bench/metrics/<metric>.py``.

The serving loop is one host thread, as a deployment's is: one
``AerialDB`` session with its ``IngestPipeline``. It serves the events of
the window in the order they were due (offloads, flush ticks, window
queries, live-map polls), blocks only where a client needs an answer, and
times every answer from the event's due time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import resource
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from bench import check, schedule as sch, work
from bench.data import make_sites
from repro.api import AerialDB, AggSpec, StoreConfig, make_pred
from repro.ingest import IngestPipeline
from repro.launch.mesh import make_edge_mesh

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(cell: str, section: str) -> list:
    """Names of the metrics of ``BENCHMARK.json``'s ``section`` that this
    cell reports: those listing it, and those listing no cells."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in bench[section]
            if cell in m.get("workloads", [cell])]


def store_config(config: dict) -> StoreConfig:
    sites = make_sites(config["store"]["n_edges"], config["site_seed"])
    return StoreConfig(sites=tuple(map(tuple, sites.tolist())),
                       **config["store"])


@dataclasses.dataclass
class Records:
    """What the window recorded, per event kind, in the kind's order.
    Times are seconds from the window's start on the host clock."""
    n_query: int
    n_poll: int
    n_shard: int
    batch: int
    n_channels: int
    n_drones: int
    width: int

    def __post_init__(self):
        q, b, k = self.n_query, self.batch, self.n_channels
        self.q_done = np.full(q, np.nan)
        self.q_host = np.full(q, np.nan)       # query() call to its return
        self.q_acked = np.zeros(q, int)        # window shards acknowledged
        self.q_ok = np.zeros(q, bool)
        self.q_count = np.zeros((q, b), np.int64)
        self.q_sum = np.zeros((q, b, k), np.float32)
        self.q_min = np.zeros((q, b, k), np.float32)
        self.q_max = np.zeros((q, b, k), np.float32)
        self.p_done = np.full(self.n_poll, np.nan)
        self.p_ok = np.zeros(self.n_poll, bool)
        self.p_submitted = np.zeros(self.n_poll, int)
        self.p_record = np.zeros((self.n_poll, self.n_drones, self.width),
                                 np.float32)
        self.p_valid = np.zeros((self.n_poll, self.n_drones), bool)
        self.s_acked_at = np.full(self.n_shard, np.nan)
        self.acked = []                        # window shards, ack order
        self.flushes = []      # (start, seconds, shards, dispatches) each
        self.late = []         # start - due after a sleep: sleep overrun
        self.behind = []       # start - due when the loop was busy
        self.errors = []
        self.compiles = 0
        self.trace_start = None                # profiler started, host s
        self.end = 0.0                         # last event served


class Session:
    """One deployment, its ingest pipeline, and the serving loop."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        mesh = None
        if config["mesh"]:
            mesh = make_edge_mesh(config["mesh"]["edge"],
                                  n_edges=config["store"]["n_edges"])
        self.db = AerialDB.open(store_config(config), mesh=mesh, seed=seed)
        self.pipe = IngestPipeline(self.db, **config["pipeline"])
        self.channels = tuple(traffic["queries"]["channels"])
        self.agg = AggSpec(channels=self.channels)

    # -- set-up ---------------------------------------------------------------

    def _submit(self, drone, seq, rows):
        self.pipe.submit_arrays(drone, seq, rows[:, 0], rows[:, 1],
                                rows[:, 2], rows[:, 3:])

    def preload(self, sched: sch.Schedule) -> None:
        """Load the preload through the pipeline in two flushes. The second
        holds the last ``2 * batch_shards - 1`` shards, which the pipeline
        cuts into one batch of each power of two up to ``batch_shards``:
        every insert program a window flush can run is compiled here."""
        r = self.config["store"]["records_per_shard"]
        n_shards = len(sched.pre_rows) // r
        tail = min(2 * self.pipe.batch_shards - 1, n_shards)
        cut = (n_shards - tail) * r
        for sl in (slice(0, cut), slice(cut, None)):
            if sl.stop == 0:
                continue
            self._submit(sched.pre_drone[sl], sched.pre_seq[sl],
                         sched.pre_rows[sl])
            out = self.pipe.flush()
            n = len(sched.pre_rows[sl])
            if out["flushed_records"] != n or self.pipe.pending:
                raise RuntimeError(f"preload flushed {out['flushed_records']}"
                                   f" of {n} records")

    def query(self, bounds: dict):
        """One window request as a client makes it: build the batch and
        query; the answer is still on the device."""
        pred = make_pred(q=len(bounds["t0"]), has_spatial=True,
                         has_temporal=True, is_and=True, **bounds)
        res, _ = self.db.query(pred, agg=self.agg)
        return res

    def fetch(self, res):
        return jax.device_get((res.count, res.vsum, res.vmin, res.vmax,
                               res.overflow))

    def warm(self, sched: sch.Schedule) -> None:
        """Run every call of the window once more than it needs to compile:
        the window's query program, the live-map read, and the flush
        scheduler, armed so that its first tick is the window's first."""
        bounds = {k: v[0] for k, v in sched.query_bounds.items()}
        for _ in range(2):
            self.fetch(self.query(bounds))
            self.pipe.latest()
        self.pipe.maybe_flush(now=sched.tick_due[0]
                              - self.pipe.flush_interval_s)

    # -- the window -----------------------------------------------------------

    def serve(self, sched: sch.Schedule, tracer=None) -> Records:
        span = tracer.span if tracer is not None else contextlib.nullcontext
        store = self.config["store"]
        rec = Records(len(sched.query_due), len(sched.poll_due),
                      len(sched.shard_due), sched.query_bounds["t0"].shape[1],
                      len(self.channels), self.config["fleet"]["n_drones"],
                      3 + store["n_values"])
        pending = []
        submitted = 0
        clock = time.perf_counter
        t0 = clock()
        for due, kind, j in zip(sched.due, sched.kind, sched.index):
            if tracer is not None:
                tracer.at(due, t0, rec)
            now = clock() - t0
            if now < due:
                with span("bench.wait"):
                    time.sleep(due - now)
                start = clock() - t0
                rec.late.append(start - due)
            else:
                start = now
                rec.behind.append(start - due)
            try:
                if kind == sch.SUBMIT:
                    with span("bench.submit"):
                        self._submit(np.full(sched.shard_seq.shape[1],
                                             sched.shard_drone[j]),
                                     sched.shard_seq[j], sched.shard_rows[j])
                    pending.append(j)
                    submitted += 1
                elif kind == sch.TICK:
                    with span("bench.flush"):
                        out = self.pipe.maybe_flush(now=sched.tick_due[j])
                    end = clock() - t0
                    if out is not None and out["flushed_shards"]:
                        rec.flushes.append((start, end - start,
                                            out["flushed_shards"],
                                            out["dispatches"]))
                        if out["flushed_shards"] != len(pending):
                            raise RuntimeError(
                                f"flush shipped {out['flushed_shards']} of "
                                f"{len(pending)} pending shards")
                        rec.s_acked_at[pending] = end
                        rec.acked += pending
                        pending = []
                elif kind == sch.QUERY:
                    rec.q_acked[j] = len(rec.acked)
                    bounds = {k: v[j] for k, v in sched.query_bounds.items()}
                    with span("bench.query"):
                        res = self.query(bounds)
                        rec.q_host[j] = clock() - t0 - start
                        with span("bench.query.block"):
                            count, vsum, vmin, vmax, ovf = self.fetch(res)
                    rec.q_done[j] = clock() - t0
                    rec.q_count[j], rec.q_sum[j] = count, vsum.reshape(
                        rec.q_sum[j].shape)
                    rec.q_min[j] = vmin.reshape(rec.q_min[j].shape)
                    rec.q_max[j] = vmax.reshape(rec.q_max[j].shape)
                    rec.q_ok[j] = not ovf.any()
                else:
                    rec.p_submitted[j] = submitted
                    with span("bench.latest"):
                        record, valid = self.pipe.latest()
                    rec.p_done[j] = clock() - t0
                    rec.p_record[j], rec.p_valid[j] = record, valid
                    rec.p_ok[j] = True
            except Exception:  # noqa: BLE001 - a failed request is counted
                rec.errors.append(f"{sch.KIND_NAMES[kind]} {j} due {due:.3f}"
                                  f" s:\n{traceback.format_exc()}")
        rec.end = clock() - t0
        return rec

    def audit(self) -> dict:
        """The guarantees the store must hold after the window."""
        state = self.db.state
        recon = self.pipe.reconcile()
        c = self.pipe.counters
        return {
            "stored_gap": abs(recon["stored_tuples"]
                              - recon["expected_tuples"]),
            "counters_gap": abs(c["accepted"] - c["flushed_records"]
                                - self.pipe.pending),
            "index_dropped": int(np.asarray(state.index.dropped).sum()),
            "ring_wrapped": int(np.asarray(state.tup_overwritten).sum()),
        }


class CompileCount:
    """Counts programs compiled or loaded from the persistent cache, from
    JAX's own monitoring events, while ``on``."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


class StallWatch:
    """What could hold the serving loop back besides its own work, over the
    window: the garbage collector's pauses, and the time the loop's thread
    waited for a CPU (the kernel's run delay) or was switched out against
    its will. Set-up's objects are frozen out of the collector's way."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    @staticmethod
    def _sched():
        try:
            with open("/proc/thread-self/schedstat") as f:
                delay_ns = int(f.read().split()[1])
        except (OSError, IndexError, ValueError):
            delay_ns = None
        return delay_ns, resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._hear)
        self._start = self._sched()
        return self

    def __exit__(self, *exc):
        end = self._sched()
        gc.callbacks.remove(self._hear)
        gc.unfreeze()
        delay = (None if end[0] is None or self._start[0] is None
                 else (end[0] - self._start[0]) / 1e6)
        pause = [p for _, p in self.pauses]
        self.summary = {
            "gc_collections": len(pause),
            "gc_full": sum(g == 2 for g, _ in self.pauses),
            "gc_pause_ms_total": 1e3 * sum(pause),
            "gc_pause_ms_max": 1e3 * max(pause, default=0.0),
            "run_delay_ms": delay,
            "involuntary_switches": end[1] - self._start[1]}


class Tracer:
    """Profiles a steady slice at the end of the window: starts the
    profiler at the first event due at or after ``start_s`` and leaves it
    running to the loop's end; every loop call gets a host span."""

    span = jax.profiler.TraceAnnotation

    def __init__(self, start_s: float, out_dir: Path):
        self.start_s, self.dir = start_s, out_dir
        self.running = False

    def at(self, due, t0, rec):
        if not self.running and due >= self.start_s:
            # Host spans and device events; no Python call tracing, whose
            # cost would land on every call of the loop.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir),
                                          profiler_options=opts)
            self.running = True
            rec.trace_start = time.perf_counter() - t0

    def stop(self) -> Path | None:
        if not self.running:
            return None
        jax.profiler.stop_trace()
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    cell: str
    config: dict
    traffic: dict
    schedule: sch.Schedule
    records: Records
    setup_s: float
    platform: str
    device_kind: str
    chips: int
    memory_peak: int
    trace: object = None


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            out_dir: Path, t_process: float, *, require_tpu: bool = True,
            config: dict | None = None, log=print):
    """Set up, serve the window, audit the store. Returns ``(run, audit)``.
    ``config`` replaces the cell's configuration (a test's tiny one)."""
    cell = load_json("cells", cell_name)
    config = config or load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{dev.platform!r}); this benchmark runs on the chip")
    if len(devices) < config["chips"]:
        raise SystemExit(f"bench: the cell needs {config['chips']} chips, "
                         f"JAX found {len(devices)}")

    gen = load_module("gen", traffic["kind"])
    sched = gen.build(config, traffic, cell, seed, seconds)
    log(json.dumps({"phase": "schedule", "queries": len(sched.query_due),
                    "shards": len(sched.shard_due),
                    "ticks": len(sched.tick_due),
                    "polls": len(sched.poll_due),
                    "preload_records": len(sched.pre_rows)}))
    counter = CompileCount()
    t = time.time()
    session = Session(config, traffic, seed)
    session.preload(sched)
    jax.block_until_ready(session.db.state)
    t_preload = time.time() - t
    session.warm(sched)
    jax.block_until_ready(session.db.state)
    log(json.dumps({"phase": "setup", "before_preload_s": t - t_process,
                    "preload_s": t_preload,
                    "warm_s": time.time() - t - t_preload}))
    tracer = None
    if trace:
        tracer = Tracer(seconds - min(10.0, seconds / 2), out_dir / "trace")
    watch = StallWatch()
    with watch:
        setup_s = time.time() - t_process
        counter.on = True
        rec = session.serve(sched, tracer)
        counter.on = False
    rec.compiles = counter.n
    trace_path = tracer.stop() if tracer else None
    audit = session.audit()
    audit_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices[:config["chips"]])
    del session
    for err in rec.errors:
        print(err, file=sys.stderr)
    late = np.asarray(rec.late) if rec.late else np.zeros(1)
    log(json.dumps({
        "phase": "window", "seconds": seconds, "served_until_s": rec.end,
        "compiles_in_window": rec.compiles,
        "generator_late_ms": {"p50": 1e3 * float(np.median(late)),
                              "p99": 1e3 * float(np.percentile(late, 99)),
                              "max": 1e3 * float(late.max())},
        "loop_behind_ms_max": 1e3 * max(rec.behind, default=0.0),
        "flushes": len(rec.flushes), "errors": len(rec.errors),
        **watch.summary}))
    # Queries centred on a shard offloaded in the window, and how many of
    # those shards were acknowledged when the query was asked.
    fresh = sched.query_fresh >= 0
    log(json.dumps({"phase": "fresh", "queries_on_window_shards":
                    int(fresh.sum()), "acknowledged_when_asked": int(np.sum(
                        fresh & (sched.query_fresh < rec.q_acked[:, None])))}))
    store = config["store"]
    filled = (len(sched.pre_rows) + np.median(rec.q_acked)
              * store["records_per_shard"]) * store["replication"]
    log(json.dumps({"phase": "work", "filled_slots": int(filled),
                    "bytes_per_request": work.query_bytes(filled,
                                                          store["n_values"]),
                    "compares_per_request": work.query_compares(
                        filled, sched.query_bounds["t0"].shape[1])}))
    reduced = None
    if trace_path is not None:
        from bench import trace as trace_mod
        reduced = trace_mod.reduce(trace_mod.load(str(trace_path)))
    run = Run(cell_name, config, traffic, sched, rec, setup_s,
              dev.platform, dev.device_kind, config["chips"], audit_mem,
              reduced)
    return run, audit


def report(run: Run, audit: dict, trace: bool) -> dict:
    """The result line: metrics by their readers, the device, and every
    number compared beside its limit."""
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name in cell_metrics(run.cell, section):
        mod = load_module("metrics", name)
        value = mod.read(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": mod.UNIT}
    rec, sched = run.records, run.schedule
    read = check.readings(sched, rec, audit, run.config["fleet"]["n_drones"],
                          tuple(run.traffic["queries"]["channels"]))
    attempted = len(sched.query_due) + len(sched.poll_due) + len(
        sched.shard_due)
    failed = (int(np.sum(~rec.q_ok)) + int(np.sum(~rec.p_ok))
              + int(np.sum(np.isnan(rec.s_acked_at))))
    device = {"platform": run.platform, "kind": run.device_kind,
              "count": run.chips, "memory_peak_bytes": int(run.memory_peak)}
    result = {"correct": check.verdict(read), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = float(np.mean(run.trace.busy_ns)) / 1e9
        device["window_s"] = float(run.trace.window_ns) / 1e9
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {k: {"value": read[k], "limit": check.LIMITS.get(k)}
                        for k in read}
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return result


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             out_dir: Path, t_process: float, **kw) -> dict:
    """One run of one cell; returns the result line as a dict."""
    run, audit = execute(cell_name, seed, seconds, trace, out_dir,
                         t_process, **kw)
    return report(run, audit, trace)
