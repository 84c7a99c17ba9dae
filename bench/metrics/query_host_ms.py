"""Median host time of a window request from building its batch until
``AerialDB.query`` returns, before the wait for the answer."""
import numpy as np

UNIT = "ms"


def read(run):
    h = run.records.q_host[np.isfinite(run.records.q_host)]
    return 1e3 * float(np.median(h)) if h.size else None
