"""Shards per device dispatch over the window's flushes, from the flush
summaries' ``flushed_shards`` and ``dispatches``."""
UNIT = "shards"


def read(run):
    f = run.records.flushes
    dispatches = sum(x[3] for x in f)
    return sum(x[2] for x in f) / dispatches if dispatches else None
