"""90th percentile over every shard offloaded in the window, from its due
offload time until the flush that made it queryable returned."""
import numpy as np

UNIT = "ms"


def read(run):
    lat = run.records.s_acked_at - run.schedule.shard_due
    lat = lat[np.isfinite(lat)]
    return 1e3 * float(np.percentile(lat, 90)) if lat.size else None
