"""90th percentile over every live-map poll of the window, from its due
time until ``IngestPipeline.latest()`` returned its arrays."""
import numpy as np

UNIT = "ms"


def read(run):
    lat = run.records.p_done - run.schedule.poll_due
    lat = lat[np.isfinite(lat)]
    return 1e3 * float(np.percentile(lat, 90)) if lat.size else None
