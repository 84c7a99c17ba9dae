"""Collective-op device time per window request (all-gather, all-reduce
and the like inside ``bench.query`` spans), averaged over the chips."""
UNIT = "ms"


def read(run):
    t = run.trace
    if t is None or "bench.query" not in t.span_collective:
        return None
    coll = t.span_collective["bench.query"]
    if not coll.size or coll.sum() <= 0:
        return None
    return 1e3 * float(coll.mean(axis=1).mean()) / 1e9
