"""90th percentile over every window request of the window, from its due
time until its answer is on the host."""
import numpy as np

UNIT = "ms"


def read(run):
    lat = run.records.q_done - run.schedule.query_due
    lat = lat[np.isfinite(lat)]
    return 1e3 * float(np.percentile(lat, 90)) if lat.size else None
