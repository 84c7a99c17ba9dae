"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload d400.query_mix --seed 7 --seconds 30 \\
        --trace 0

From the root of a checkout. Makes the cell's data and traffic from
``--seed``, loads and warms the deployment, serves the window for
``--seconds``, checks every answer against the plain reference, and prints
one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` when traced, and ``checks``, each number compared beside its
limit, last. Earlier lines say how late the loop ran and how many programs
compiled inside the window (there should be none). Refuses to run, exiting
nonzero with no result line, where JAX finds no TPU or fewer chips than the
cell needs.
"""

from __future__ import annotations

import os
import time


def _process_start() -> float:
    """Wall-clock time at which this process started."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


T_PROCESS = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The TPU runtime would log to a fixed path under /tmp; write nothing there.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=str(ROOT / "bench" / "out"),
                    help="directory for traces")
    args = ap.parse_args(argv)

    from bench.cache import use_compile_cache
    use_compile_cache()
    from bench.harness import run_cell

    out = Path(args.out) / f"{args.workload}-{args.seed}-{args.trace}"
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), out, T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
