"""The benchmark's own tests run on the CPU, with four virtual devices for
the federated cell: ``python -m pytest bench/tests`` from the checkout."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


import pytest  # noqa: E402


@pytest.fixture
def tiny():
    return tiny_config


def tiny_config(name: str) -> dict:
    """The cell's configuration at a size the CPU runs in seconds: 8 edges,
    16 drones, two preloaded rounds, a mission clock fast enough to offload
    some shards in a 3 s window. Shapes of records and queries unchanged."""
    from bench.harness import load_json
    cfg = load_json("configs", name)
    cfg["store"].update(n_edges=8, tuple_capacity=4096,
                        max_shards_per_query=64, max_drones=16)
    cfg["fleet"]["n_drones"] = 16
    cfg["pipeline"]["batch_shards"] = 4
    cfg["preload_rounds"] = 2
    cfg["mission_clock_speedup"] = 100
    return cfg
