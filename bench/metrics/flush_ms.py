"""Median host time of the window's flushes that shipped shards: the
``maybe_flush`` call, coalescing, dispatch and the block at its end."""
import numpy as np

UNIT = "ms"


def read(run):
    f = run.records.flushes
    return 1e3 * float(np.median([x[1] for x in f])) if f else None
