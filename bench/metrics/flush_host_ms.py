"""Median host time of a flush that shipped shards, less its wait for the
device: the ``aerialdb.ingest.flush`` span minus its
``aerialdb.ingest.block`` child, over the flushes of the traced slice that
dispatched."""
import numpy as np

from bench import scopes

UNIT = "ms"


def read(run):
    s = scopes.of(run)
    if s is None or "aerialdb.ingest.flush" not in s.span_times:
        return None
    flush = s.span_times["aerialdb.ingest.flush"]
    shipped = s.host_ns("aerialdb.ingest.flush",
                        ("aerialdb.ingest.dispatch",)) > 0
    if not shipped.any():
        return None
    host = (flush[:, 1] - flush[:, 0]
            - s.host_ns("aerialdb.ingest.flush", ("aerialdb.ingest.block",)))
    return 1e3 * float(np.median(host[shipped])) / 1e9
