"""Device time of the flushes in the traced slice (the longest chip's busy
time inside each ``bench.flush`` span) per shard they inserted."""
UNIT = "us/shard"


def read(run):
    t, rec = run.trace, run.records
    if t is None or "bench.flush" not in t.span_busy or rec.trace_start is None:
        return None
    shards = sum(f[2] for f in rec.flushes if f[0] >= rec.trace_start)
    busy = t.span_busy["bench.flush"].max(axis=1).sum()
    return 1e6 * busy / 1e9 / shards if shards and busy > 0 else None
