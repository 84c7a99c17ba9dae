"""Read the control beside the program, on the chip, at a cell's own size.

    python3 bench/control.py --workload d400.query_mix --seconds 30 \\
        --seeds 101 102 103

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it, then the numbers compared for the program and for each control
of ``check.CONTROLS`` in its place (the plain reference over the same
records in bfloat16; the sums alone over values in bfloat16). The program
has to come out correct and each control not. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench.cache import use_compile_cache
    use_compile_cache()
    from bench import check, harness

    for seed in args.seeds:
        run, audit = harness.execute(args.workload, seed, args.seconds, False,
                                     ROOT / "bench" / "out", time.time())
        ch = tuple(run.traffic["queries"]["channels"])
        n = run.config["fleet"]["n_drones"]
        for who in ("program", *check.CONTROLS):
            control = None if who == "program" else who
            read = check.readings(run.schedule, run.records, audit, n, ch,
                                  control)
            print(json.dumps({"seed": seed, "who": who,
                              "correct": check.verdict(read), **read}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
