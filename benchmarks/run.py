"""Benchmark driver: one module per paper table/figure (DESIGN.md §7).
Prints ``name,us_per_call,derived`` CSV rows; also usable per-figure:
``python -m benchmarks.run --only fig12``.

``--json`` additionally writes one machine-readable ``BENCH_<fig>.json`` per
figure run (rows + wall-clock + host/config fingerprint), so the perf
trajectory is tracked across PRs — CI runs the scan-batch family with
``--only fig5_scan_batch --json`` and archives the file as an artifact.
"""

import argparse
import importlib
import json
import platform
import sys
import time

FIGS = ["fig5_membership", "fig5_scan_batch", "fig7_insertion_scaling",
        "fig8_insertion_baselines", "fig9_planners", "fig10_concurrency",
        "fig11_mixed_queries", "fig12_query_baselines", "fig13_locality",
        "fig14_resilience", "fig15_sustained_ingest", "fig17_churn_soak",
        "fig18_streaming_ingest", "fig19_chaos_soak"]


def _config_fingerprint() -> dict:
    """Host/config context stored with every JSON result so cross-PR
    comparisons know what they are comparing."""
    import jax
    return {
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter, e.g. fig12")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<fig>.json per figure (rows + "
                         "wall-clock + config)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import common

    print(f"# device={common.device_tag()}")
    print("name,us_per_call,derived")
    t0 = time.time()
    config = _config_fingerprint() if args.json else None
    ran = 0
    for mod_name in FIGS:
        if args.only and args.only not in mod_name:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        print(f"# --- {mod_name} ---", flush=True)
        common.ROWS.clear()
        fig_t0 = time.time()
        mod.run()
        if args.json:
            out = {
                "fig": mod_name,
                "wall_s": round(time.time() - fig_t0, 2),
                "config": config,
                "rows": list(common.ROWS),
            }
            path = f"BENCH_{mod_name}.json"
            with open(path, "w") as f:
                json.dump(out, f, indent=2)
            print(f"# wrote {path} ({len(out['rows'])} rows)", flush=True)
        ran += 1
    if not ran:
        print(f"# no figure matches --only {args.only!r}", file=sys.stderr)
        sys.exit(2)
    print(f"# total_wall_s={time.time() - t0:.0f}")


if __name__ == "__main__":
    main()
