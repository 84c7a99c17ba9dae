"""Tour of the composable query/aggregation API (paper §4.5 workload shapes).

Walks every aggregate op (count / sum / min / max / mean), channel selection,
the AND and OR combinators, shard-id point lookups, batching, and the
failure-handling session methods — all through the ``repro.api`` facade, on a
small single-device deployment.

    PYTHONPATH=src python examples/query_api_tour.py
"""

import numpy as np

from repro.api import AGG_OPS, AerialDB, Query
from repro.data.synthetic import DroneFleet
from repro.launch.compile_cache import enable_compile_cache


def show(label, res, spec):
    view = {op: float(np.asarray(v)[0]) for op, v in res.view(spec).items()}
    cells = "  ".join(f"{op}={val:10.2f}" for op, val in view.items())
    print(f"  {label:<34} {cells}")


def main():
    # --- open + load: the facade owns state/alive/key plumbing ---
    db = AerialDB.open(n_edges=8, tuple_capacity=1 << 12, index_capacity=1024,
                       max_shards_per_query=64, records_per_shard=20)
    fleet = DroneFleet(12, records_per_shard=20, seed=7)
    payloads, metas = fleet.next_rounds(5)
    db.ingest_rounds(payloads, metas)
    t_max = float(payloads[..., 0].max())
    print(f"loaded {int(np.asarray(db.state.tup_count).sum())} tuple replicas "
          f"over {db.cfg.n_edges} edges, t in [0, {t_max:.0f}]s\n")

    # --- every aggregate, one channel at a time ---
    print("aggregates over the whole deployment (per sensor channel):")
    window = Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, t_max)
    for ch in range(db.cfg.n_values):
        q = window.agg(*AGG_OPS, channel=ch)
        res, _ = db.query(q)
        show(f"channel {ch}: all ops", res, q.spec)

    # --- single-op requests: .view projects what was asked for ---
    print("\nsingle-op requests:")
    for op in AGG_OPS:
        q = window.agg(op, channel=2)
        res, _ = db.query(q)
        show(f'.agg("{op}", channel=2)', res, q.spec)

    # --- fused multi-channel: every channel's aggregates from ONE scan ---
    print("\nmulti-channel (one scan of the log answers all channels):")
    q_mc = window.agg("count", "mean", "max",
                      channels=tuple(range(db.cfg.n_values)))
    res, _ = db.query(q_mc)
    view = res.view(q_mc.spec)               # count (Q,), others (Q, K)
    for ch in range(db.cfg.n_values):
        print(f"  channel {ch}: count={int(view['count'][0]):6d} "
              f"mean={float(view['mean'][0, ch]):8.2f} "
              f"max={float(view['max'][0, ch]):8.2f}")

    # --- AND combinator: tuples must satisfy every clause ---
    print("\ncombinators:")
    left = Query().bbox(12.90, 13.00, 77.50, 77.65)
    right = Query().time(0.0, t_max / 3)
    q_and = (left & right).agg("count", "mean")
    res, _ = db.query(q_and)
    show("bbox & time  (AND)", res, q_and.spec)

    # --- OR combinator: tuples may satisfy any clause ---
    q_or = (left | right).agg("count", "mean")
    res, _ = db.query(q_or)
    show("bbox | time  (OR)", res, q_or.spec)

    # --- shard-id point lookup chained with a time window ---
    q_sid = Query().shard(3, 1).time(0.0, t_max).agg("count", "min", "max")
    res, _ = db.query(q_sid)
    show("shard(3,1) & time", res, q_sid.spec)

    # --- a batch: one compiled scan answers all three spatial sizes ---
    print("\nbatched queries (one dispatch):")
    deg = 1.0 / 111.0
    # Center the boxes on a really-inserted tuple (analysts query where
    # drones actually flew), so the small windows are non-empty.
    anchor = payloads.reshape(-1, payloads.shape[-1])[100]
    center_lat, center_lon = float(anchor[1]), float(anchor[2])
    sizes = {"200m": 0.2 * deg, "1km": deg, "5km": 5 * deg}
    pred, spec = Query.batch(*[
        Query().bbox(center_lat - d / 2, center_lat + d / 2,
                     center_lon - d / 2, center_lon + d / 2)
               .time(0.0, t_max).agg("count", "mean")
        for d in sizes.values()])
    res, info = db.query((pred, spec))
    for i, name in enumerate(sizes):
        print(f"  {name:>5} box: count={int(res.count[i]):6d} "
              f"mean={float(res.vmean[i]):8.2f} "
              f"edges={int(info.subquery_edges[i])}")

    # --- failures: the session re-plans around dead edges ---
    print("\nresilience:")
    q = window.agg("count", channel=0)
    before, _ = db.query(q)
    db.fail_edges(1, 5)
    during, info = db.query(q)
    db.recover_edges(1, 5)
    after, _ = db.query(q)
    print(f"  count before/during/after 2 edge failures: "
          f"{int(before.count[0])}/{int(during.count[0])}/"
          f"{int(after.count[0])} "
          f"(replication covers dead edges; broadcast={bool(info.broadcast[0])})")

    # --- validation: inverted ranges raise instead of matching nothing ---
    print("\nvalidation:")
    try:
        Query().bbox(13.10, 12.85, 77.45, 77.75)
    except ValueError as e:
        print(f"  inverted bbox      -> ValueError: {str(e)[:58]}...")
    try:
        Query().time(600.0, 0.0)
    except ValueError as e:
        print(f"  inverted time      -> ValueError: {str(e)[:58]}...")
    try:
        db.query(window.agg("count", channel=99))
    except ValueError as e:
        print(f"  channel overflow   -> ValueError: {str(e)[:58]}...")
    try:
        (left & Query().time(0, 1)) | Query().shard(0, 0)
    except ValueError as e:
        print(f"  (A&B)|C            -> ValueError: {str(e)[:58]}...")


if __name__ == "__main__":
    enable_compile_cache()
    main()
