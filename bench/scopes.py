"""The program's own names in a profiler trace, beside ``bench/trace.py``.

The program labels its work two ways (``core/datastore.py``,
``api/session.py``, ``ingest/pipeline.py``):

* host spans named ``aerialdb.*`` (``jax.profiler.TraceAnnotation``), some
  tagged with a sequence number (``q=<n>``, ``flush=<n>``);
* device scopes ``query.*`` / ``insert.*`` (``jax.named_scope``), which end
  up in the ``op_name`` metadata of the HLO instructions they emit.

``load`` reads both from one ``.xplane.pb`` and ``reduce`` adds, to what
``trace.reduce`` gives (every field of it unchanged):

* device time per scope inside each instance of each ``bench.*`` span,
  like ``span_busy``; the busy time no scope covers is ``UNSCOPED``;
* the start and end of every program span, and the device time and host
  time of the program inside any span (``host_ns``);
* idle device time by the innermost span of either kind;
* device time per operation named ``program:scope/op``.

Where a device op's scope comes from. The TPU's op events name the HLO
instruction that ran; the ``tf_op`` stat of their metadata, which holds its
``op_name``, is empty on some fusions (the query's OR-list scatter among
them), so it is not used. The trace also keeps each program's optimized HLO,
with every instruction's metadata, on its ``/host:metadata`` plane (``Hlo
Proto``). An instruction's scope is the innermost ``query.*``/``insert.*``
component of its own ``op_name``; an instruction without one (a fusion,
a while loop) takes the scope most instructions of the computations it calls
carry. A program the trace holds no HLO for leaves its ops unscoped.

``of(run)`` loads and reduces the trace of a ``bench/run.py`` run once, for
every metric that reads it, and prints one ``scopes`` line: the scoped
breakdown and the unscoped share of the window requests' device time.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import re
import sys
from pathlib import Path

import numpy as np

from bench import trace

PROGRAM_PREFIX = "aerialdb."
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
_SCOPE = re.compile(r"^(?:query|insert)\.[a-z_]+$")
_INSTR = re.compile(r"^\s*(?:ROOT )?([\w.\-]+) = ")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost_scope(op_name: str):
    """The last ``query.*``/``insert.*`` component of an ``op_name`` path."""
    found = [p for p in op_name.split("/") if _SCOPE.match(p)]
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """``{instruction: scope}`` of one program's HLO text (metadata
    printed). An instruction without a scope of its own takes the one most
    instructions of its called computations carry, recursively."""
    own, calls, members = {}, {}, collections.defaultdict(list)
    comp = None
    for line in text.split("\n"):
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = innermost_scope(op.group(1)) if op else None
        called = _CALLS.findall(line)
        for b in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        calls[name] = called
        members[comp].append(name)

    memo = {}

    def scope(name):
        if name in memo:
            return memo[name]
        s = own.get(name)
        if s is None and calls.get(name):
            votes = collections.Counter(
                scope(i) for c in calls[name] for i in members.get(c, ()))
            votes.pop(None, None)
            s = votes.most_common(1)[0][0] if votes else None
        memo[name] = s
        return s

    return {name: scope(name) for name in own}


# -- reading the .xplane.pb ---------------------------------------------------

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of one protobuf message: ints for varint
    fields, memoryviews for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def program_hlo(data: bytes) -> dict:
    """``{program ("jit_f(<id>)"): HLO text with metadata}`` from the
    ``Hlo Proto`` stats of the trace's metadata plane (``XSpace.planes`` =
    1; ``XPlane``: name = 2, event_metadata = 4; map entry value = 2;
    ``XEventMetadata``: name = 2, stats = 5; ``XStat.bytes_value`` = 6;
    ``HloProto.hlo_module`` = 1)."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    out = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == METADATA_PLANE
                   for f, v in parts):
            continue
        for f, entry in parts:
            if f != 4:
                continue
            meta = dict(_fields(dict(_fields(entry))[2]))
            stat = dict(_fields(meta.get(5, b"")))
            hlo = dict(_fields(stat.get(6, b""))).get(1)
            if hlo is None:
                continue
            module = _jax.HloModule.from_serialized_hlo_module_proto(
                bytes(hlo))
            out[bytes(meta[2]).decode()] = module.to_string(opts)
    return out


def load(path: str) -> dict:
    """What ``trace.load`` gives, with each device op as ``[start, dur,
    name, scope]`` (``scope`` None where it has none), plus
    ``"program_spans"``: ``[[start, dur, name]]`` of the ``aerialdb.*``
    host spans."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    scopes = {prog: hlo_scopes(text)
              for prog, text in program_hlo(data).items()}
    events = trace.load(path)
    program = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program += [[ev.start_ns, ev.duration_ns, ev.name]
                            for ev in line.events
                            if ev.name.startswith(PROGRAM_PREFIX)]
            continue
        if plane.name not in events["devices"]:
            continue
        mods = sorted([ev.start_ns, ev.start_ns + ev.duration_ns, ev.name]
                      for line in plane.lines
                      if line.name == trace.MODULES_LINE
                      for ev in line.events)
        starts = np.array([m[0] for m in mods], float)
        for op in events["devices"][plane.name]["ops"]:
            k = np.searchsorted(starts, op[0], side="right") - 1
            inside = k >= 0 and op[0] < mods[k][1]
            table = scopes.get(mods[k][2], {}) if inside else {}
            op.append(table.get(op[2]))
    events["program_spans"] = sorted(program)
    return events


# -- the reduction ------------------------------------------------------------

@dataclasses.dataclass
class Scoped(trace.Reduced):
    """``trace.Reduced`` (every field as ``trace.reduce`` gives it) and:

    scope_busy: {bench span: {scope: (n, n_dev)}} device time per scope
        inside each span instance; ``UNSCOPED`` is the busy time no scope
        covers.
    span_times: {name: (n, 2)} start and end of each instance of each span,
        ``bench.*`` and ``aerialdb.*``.
    idle_by_innermost: {name: ns} idle device time (mean over devices) by
        the innermost span of either kind.
    scoped_ops: {"program:scope/op": ns} device time over all devices.
    """
    scope_busy: dict = dataclasses.field(default_factory=dict)
    span_times: dict = dataclasses.field(default_factory=dict)
    idle_by_innermost: dict = dataclasses.field(default_factory=dict)
    scoped_ops: dict = dataclasses.field(default_factory=dict)

    def host_ns(self, outer: str, inner) -> np.ndarray:
        """For each instance of span ``outer`` (either kind), the host time
        of the program spans named in ``inner`` that lie inside it."""
        out_arr = self.span_times.get(outer, np.empty((0, 2)))
        total = np.zeros(len(out_arr))
        for name in inner:
            arr = self.span_times.get(name, np.empty((0, 2)))
            for i, (a, b) in enumerate(out_arr):
                keep = (arr[:, 0] >= a) & (arr[:, 1] <= b)
                total[i] += np.sum(arr[keep, 1] - arr[keep, 0])
        return total

    def has_scopes(self, span: str) -> bool:
        busy = self.scope_busy.get(span, {})
        return any(v.sum() > 0 for k, v in busy.items() if k != UNSCOPED)

    def unscoped_share(self, span: str):
        """Share of the device time inside ``span`` that no scope covers."""
        busy = self.span_busy.get(span)
        if busy is None or busy.sum() <= 0:
            return None
        return float(self.scope_busy[span][UNSCOPED].sum() / busy.sum())

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.scoped_ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_innermost.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / 1e9 / len(self.devices)]
                               for n, v in ops],
                "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def reduce(events: dict) -> Scoped:
    base = trace.reduce(events)
    spans = [sp for sp in events["spans"]
             if sp[2].startswith(trace.SPAN_PREFIX)]
    program = events.get("program_spans", [])
    w0 = min(s for s, _, _ in spans)
    w1 = max(s + d for s, d, _ in spans)
    times = collections.defaultdict(list)
    for s, d, n in spans + program:
        times[n].append((s, s + d))
    times = {n: np.array(v, float) for n, v in times.items()}
    bench_arr = {n: times[n] for n in base.span_busy}
    cuts, seg_names = trace._innermost(spans + program)

    n_dev = len(base.devices)
    scope_busy = collections.defaultdict(dict)
    scoped_ops = collections.defaultdict(float)
    idle = collections.defaultdict(float)
    for j, dev in enumerate(base.devices):
        ops = events["devices"][dev]["ops"]
        st = np.array([o[0] for o in ops], float)
        en = st + np.array([o[1] for o in ops], float)
        keep = (en > w0) & (st < w1)
        st, en = np.clip(st[keep], w0, w1), np.clip(en[keep], w0, w1)
        kept = [o for o, k in zip(ops, keep) if k]
        scope = np.array([o[3] if len(o) > 3 and o[3] else UNSCOPED
                          for o in kept], object)
        busy = trace._Covered(*trace._union(st, en))
        scoped = trace._Covered(*trace._union(st[scope != UNSCOPED],
                                              en[scope != UNSCOPED]))
        covers = {s: trace._Covered(*trace._union(st[scope == s],
                                                  en[scope == s]))
                  for s in set(scope) - {UNSCOPED}}
        for n, arr in bench_arr.items():
            per = scope_busy[n]
            for s, cov in covers.items():
                per.setdefault(s, np.zeros((len(arr), n_dev)))[:, j] = \
                    cov.between(arr[:, 0], arr[:, 1])
            per.setdefault(UNSCOPED, np.zeros((len(arr), n_dev)))[:, j] = (
                busy.between(arr[:, 0], arr[:, 1])
                - scoped.between(arr[:, 0], arr[:, 1]))
        mods = sorted(events["devices"][dev]["modules"])
        m_start = np.array([m[0] for m in mods], float)
        m_end = m_start + np.array([m[1] for m in mods], float)
        k = np.searchsorted(m_start, st, side="right") - 1
        for o, s, a, b, i in zip(kept, scope, st, en, k):
            inside = i >= 0 and a < m_end[i]
            label = o[2] if s == UNSCOPED else f"{s}/{o[2]}"
            scoped_ops[f"{mods[i][2]}:{label}" if inside else label] += b - a
        seg_idle = np.diff(cuts) - busy.between(cuts[:-1], cuts[1:])
        for n, v in zip(seg_names, seg_idle):
            idle[n] += v / n_dev
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(trace.Reduced)}
    return Scoped(**fields, scope_busy=dict(scope_busy), span_times=times,
                  idle_by_innermost=dict(idle), scoped_ops=dict(scoped_ops))


# -- one bench/run.py run -----------------------------------------------------

def _trace_file(run):
    """The ``.xplane.pb`` ``bench/run.py`` wrote for this run: ``--out`` /
    ``<cell>-<seed>-1`` / ``trace`` (``harness.Tracer``), read from the
    process's own arguments, since ``harness.Run`` keeps no path."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--out", default=str(Path(__file__).parent / "out"))
    args, _ = ap.parse_known_args(sys.argv[1:])
    if args.workload != run.cell or args.seed is None:
        return None
    found = sorted((Path(args.out) / f"{run.cell}-{args.seed}-1" / "trace")
                   .glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def of(run):
    """The scoped reduction of ``run``'s trace, made once and kept on the
    run; None where the run was not traced."""
    if not hasattr(run, "scoped"):
        path = _trace_file(run) if run.trace is not None else None
        run.scoped = reduce(load(str(path))) if path else None
        if run.scoped is not None:
            line = {"phase": "scopes", "unscoped_share.query":
                    run.scoped.unscoped_share("bench.query"),
                    **run.scoped.breakdown()}
            print(json.dumps(line), flush=True)
    return run.scoped


def per_request_ms(run, scopes) -> float | None:
    """Median over the window requests of the longest chip's device time
    under ``scopes`` inside the request's ``bench.query`` span; the requests
    are those ``query_device_ms`` counts (some device time at all)."""
    s = of(run)
    if s is None or not s.has_scopes("bench.query"):
        return None
    per = s.scope_busy["bench.query"]
    zero = np.zeros_like(per[UNSCOPED])
    got = sum((per.get(name, zero) for name in scopes), zero).max(axis=1)
    busy = s.span_busy["bench.query"].max(axis=1)
    return 1e3 * float(np.median(got[busy > 0])) / 1e9


def host_ms(run, outer: str, inner) -> float | None:
    """Median over the instances of ``outer`` holding any of the program
    spans ``inner`` of their host time in them."""
    s = of(run)
    if s is None:
        return None
    host = s.host_ns(outer, inner)
    host = host[host > 0]
    return 1e3 * float(np.median(host)) / 1e9 if host.size else None
