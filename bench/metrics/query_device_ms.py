"""Median device time of a window request: the busy time of the chip that
took longest, inside the request's ``bench.query`` span."""
import numpy as np

UNIT = "ms"


def device_s(run):
    t = run.trace
    if t is None or "bench.query" not in t.span_busy:
        return None
    per = t.span_busy["bench.query"].max(axis=1)
    per = per[per > 0]
    return float(np.median(per)) / 1e9 if per.size else None


def read(run):
    s = device_s(run)
    return None if s is None else 1e3 * s
