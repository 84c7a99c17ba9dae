"""Share of the least time a window request needs (every filled log slot
read once, at the chips' HBM bandwidth: ``bench/work.py``) in its device
time (``query_device_ms``)."""
import numpy as np

from bench import work
from bench.harness import load_module

UNIT = "%"


def read(run):
    dev = load_module("metrics", "query_device_ms").device_s(run)
    if dev is None:
        return None
    store = run.config["store"]
    records = (len(run.schedule.pre_rows)
               + np.median(run.records.q_acked) * store["records_per_shard"])
    least = work.least_query_s(records * store["replication"],
                               store["n_values"], run.chips, run.device_kind)
    return 100.0 * least / dev
