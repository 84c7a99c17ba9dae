"""Find the highest query rate a cell sustains, on the chip, once.

    python3 bench/sweep.py --workload d400.query_mix --seed 5 \\
        --rates 2 2.5 3 3.5 4 --segment 15

One set-up, then one window in which the cell's traffic runs unchanged
except the window requests, whose rate steps through ``--rates``, each for
``--segment`` seconds. Prints, per rate, the request latencies and whether
the backlog grew: the last third of a segment's requests waiting far longer
than the first third. The cell file then takes 0.8 of the highest rate
whose backlog did not grow.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--segment", type=float, default=15.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench.cache import use_compile_cache
    use_compile_cache()
    from bench import harness
    from bench.schedule import Schedule, poisson_pattern

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench: JAX found no TPU; the sweep runs on the chip")
    cell = harness.load_json("cells", args.workload)
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    gen = harness.load_module("gen", traffic["kind"])
    seg, rates = args.segment, args.rates
    total = seg * len(rates)
    base = gen.build(config, traffic, dict(cell, query_rate_per_s=max(rates)),
                     args.seed, total)
    dues, rows = [], []
    for k, rate in enumerate(rates):
        due = poisson_pattern(rate, seg, traffic["pattern_seed"], 0.0)
        dues.append(due + k * seg)
        rows.append(np.arange(len(due)) % len(base.query_due))
    rows = np.concatenate(rows)
    fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)
              if f.name not in ("due", "kind", "index")}
    fields.update(query_due=np.concatenate(dues),
                  query_bounds={k: v[rows] for k, v in
                                base.query_bounds.items()},
                  query_fresh=base.query_fresh[rows])
    sched = Schedule(**fields)

    session = harness.Session(config, traffic, args.seed)
    t = time.time()
    session.preload(sched)
    session.warm(sched)
    print(json.dumps({"phase": "setup", "seconds": time.time() - t}),
          flush=True)
    rec = session.serve(sched)
    lat = rec.q_done - sched.query_due
    for k, rate in enumerate(rates):
        sel = (sched.query_due >= k * seg) & (sched.query_due < (k + 1) * seg)
        x = lat[sel]
        third = max(len(x) // 3, 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": int(sel.sum()),
            "p50_ms": 1e3 * float(np.median(x)),
            "p90_ms": 1e3 * float(np.percentile(x, 90)),
            "first_third_ms": 1e3 * float(np.median(x[:third])),
            "last_third_ms": 1e3 * float(np.median(x[-third:])),
            "service_ms": 1e3 * float(np.min(x))}), flush=True)
    ing = rec.s_acked_at - sched.shard_due
    pol = rec.p_done - sched.poll_due
    print(json.dumps({"ingest_p90_ms": 1e3 * float(np.nanpercentile(ing, 90)),
                      "latest_p90_ms": 1e3 * float(np.nanpercentile(pol, 90)),
                      "flushes": len(rec.flushes), "errors": len(rec.errors)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
