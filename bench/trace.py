"""Reduce a profiler trace to what the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain events; ``reduce`` works on
those alone, so a small recorded slice (``bench/tests/data``) checks it:

* the busy union of each device: the time in which an operation of the
  device's op line ran, clipped to the traced window;
* device time inside each host span of the serving loop (``bench.*``
  ``TraceAnnotation`` spans), and collective time inside them;
* device time per program (XLA module) and per operation, each operation
  named ``program:op`` by the program it ran in;
* idle device time, split by the innermost host span it fell in.

The traced window runs from the first host span's start to the last one's
end. Times are in nanoseconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
NO_SPAN = "outside bench spans"


def short_name(name: str) -> str:
    """``%fusion.5 = pred[...] fusion(...)`` -> ``fusion.5``;
    ``jit_step(1234)`` -> ``jit_step``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str) -> dict:
    """Plain events of one ``.xplane.pb``: ``{"spans": [[start, dur,
    name]], "devices": {plane: {"ops": [[start, dur, name]], "async": [...],
    "modules": [...]}}}``, keeping host events named ``bench.*`` and the
    op, async-op and module lines of every ``/device:`` plane, each name
    shortened by ``short_name``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    lines = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                key = lines.get(line.name)
                if key:
                    dev[key] = [[ev.start_ns, ev.duration_ns,
                                 short_name(ev.name)] for ev in line.events]
            if dev["ops"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.start_ns, ev.duration_ns, ev.name]
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    return {"spans": sorted(spans), "devices": devices}


def _union(starts, ends):
    """Merge intervals; returns sorted disjoint (starts, ends)."""
    if len(starts) == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    idx = np.nonzero(new)[0]
    return s[idx], np.maximum.reduceat(e, idx)


class _Covered:
    """Covered time of a disjoint interval set before any instant."""

    def __init__(self, starts, ends):
        self.s, self.e = starts, ends
        self.before = np.r_[0.0, np.cumsum(ends - starts)[:-1]]

    def upto(self, t):
        t = np.asarray(t, float)
        if not len(self.s):
            return np.zeros_like(t)
        i = np.searchsorted(self.s, t, side="right") - 1
        j = np.maximum(i, 0)
        got = self.before[j] + np.clip(t - self.s[j], 0, self.e[j] - self.s[j])
        return np.where(i >= 0, got, 0.0)

    def between(self, a, b):
        return self.upto(b) - self.upto(a)


@dataclasses.dataclass
class Reduced:
    """What the metric readers take from a trace.

    window_ns: length of the traced window. devices: plane names.
    busy_ns: (n_dev,) busy union per device inside the window.
    span_busy / span_collective: {name: (n, n_dev)} device busy time and
        collective-op time inside each span.
    modules: {name: (count, ns)} device time per program over all devices.
    ops: {name: ns} device time per operation over all devices.
    idle_by_span: {name: ns} idle device time (mean over devices) by the
        innermost host span it fell in.
    """
    window_ns: float
    devices: list
    busy_ns: np.ndarray
    span_busy: dict
    span_collective: dict
    modules: dict
    ops: dict
    idle_by_span: dict

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / 1e9 / len(self.devices)]
                               for n, v in ops],
                "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def _innermost(spans: list):
    """Cut the timeline at every span boundary; returns the cut points and,
    for each elementary segment between two cuts, the name of the innermost
    (latest-started) span covering it, or ``NO_SPAN``."""
    cuts = np.unique(np.array([[s, s + d] for s, d, _ in spans],
                              float).reshape(-1))
    names = []
    active = []                       # (start, end, name), newest last
    events = sorted([(s, 1, s + d, n) for s, d, n in spans])
    k = 0
    for a in cuts[:-1]:
        while k < len(events) and events[k][0] <= a:
            active.append(events[k])
            k += 1
        active = [ev for ev in active if ev[2] > a]
        names.append(active[-1][3] if active else NO_SPAN)
    return cuts, names


def reduce(events: dict) -> Reduced:
    spans = [sp for sp in events["spans"] if sp[2].startswith(SPAN_PREFIX)]
    if not spans or not events["devices"]:
        raise ValueError("trace has no bench spans or no device op line")
    w0 = min(s for s, _, _ in spans)
    w1 = max(s + d for s, d, _ in spans)
    by_name = defaultdict(list)
    for s, d, n in spans:
        by_name[n].append((s, s + d))
    span_arr = {n: np.array(v, float) for n, v in by_name.items()}
    cuts, seg_names = _innermost(spans)

    devices = sorted(events["devices"])
    busy = np.zeros(len(devices))
    span_busy = {n: np.zeros((len(v), len(devices)))
                 for n, v in span_arr.items()}
    span_coll = {n: np.zeros((len(v), len(devices)))
                 for n, v in span_arr.items()}
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(float)
    idle = defaultdict(float)
    for j, dev in enumerate(devices):
        op = events["devices"][dev]["ops"]
        st = np.array([o[0] for o in op], float)
        en = st + np.array([o[1] for o in op], float)
        keep = (en > w0) & (st < w1)
        st, en = np.clip(st[keep], w0, w1), np.clip(en[keep], w0, w1)
        names = [o[2] for o, k in zip(op, keep) if k]
        cov = _Covered(*_union(st, en))
        busy[j] = cov.upto(w1) - cov.upto(w0)
        # Collectives run as synchronous ops or as async pairs whose
        # transfer shows on the async line; either counts.
        both = [o for o in op + events["devices"][dev].get("async", [])
                if COLLECTIVE.search(o[2])]
        cs = np.array([o[0] for o in both], float)
        ce = cs + np.array([o[1] for o in both], float)
        coll = _Covered(*_union(np.clip(cs, w0, w1), np.clip(ce, w0, w1)))
        for n, arr in span_arr.items():
            span_busy[n][:, j] = cov.between(arr[:, 0], arr[:, 1])
            span_coll[n][:, j] = coll.between(arr[:, 0], arr[:, 1])
        mods = sorted(events["devices"][dev]["modules"])
        for s, d, n in mods:
            if s + d > w0 and s < w1:
                modules[n][0] += 1
                modules[n][1] += min(s + d, w1) - max(s, w0)
        m_start = np.array([m[0] for m in mods], float)
        m_end = m_start + np.array([m[1] for m in mods], float)
        k = np.searchsorted(m_start, st, side="right") - 1
        for n, a, b, i in zip(names, st, en, k):
            inside = i >= 0 and a < m_end[i]
            ops[f"{mods[i][2]}:{n}" if inside else n] += b - a
        seg_idle = np.diff(cuts) - cov.between(cuts[:-1], cuts[1:])
        for n, v in zip(seg_names, seg_idle):
            idle[n] += v / len(devices)
    return Reduced(window_ns=w1 - w0, devices=devices, busy_ns=busy, span_busy=span_busy,
                   span_collective=span_coll,
                   modules={n: tuple(v) for n, v in modules.items()},
                   ops=dict(ops), idle_by_span=dict(idle))
