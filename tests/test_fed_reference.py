"""The federated deployment against the plain reference, as the benchmark
serves it.

Each benchmark cell (``d400.query_mix`` on one device,
``d400-fed4.query_mix_k80`` on the ``(4,)`` mesh of the four virtual CPU
devices) runs through the benchmark's own path: ``AerialDB.open`` with its
``IngestPipeline``, the open-loop serving loop, then ``bench/check.py``'s
comparison. The configuration is the cell's own, cut to the benchmark
tests' tiny size (8 edges, 16 drones, two preloaded rounds). Every window
request and live-map poll must agree with ``bench/ref.py`` over exactly
the records acknowledged or submitted before it, within the limits that
decide ``correct`` on the chip, and the store must hold the configuration's
guarantees.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, harness  # noqa: E402
from bench.tests.conftest import tiny_config  # noqa: E402

SEED = 2**31 + 1616           # larger than 32 signed bits hold


@pytest.mark.parametrize("cell, config", [
    ("d400.query_mix", "d400"),
    ("d400-fed4.query_mix_k80", "d400-fed4"),
])
def test_served_answers_match_the_reference(cell, config, tmp_path):
    tiny = tiny_config(config)
    run, audit = harness.execute(cell, SEED, 3.0, False, tmp_path,
                                 time.time(), require_tpu=False, config=tiny,
                                 log=lambda line: None)
    rec, sched = run.records, run.schedule
    assert not rec.errors
    # Every request and poll was answered, none with an overflow.
    assert rec.q_ok.all() and rec.p_ok.all()
    assert np.isfinite(rec.s_acked_at).all()
    read = check.readings(sched, rec, audit, tiny["fleet"]["n_drones"],
                          tuple(run.traffic["queries"]["channels"]))
    assert read["compared_requests"] == len(sched.query_due) > 0
    assert read["compared_polls"] == len(sched.poll_due) > 0
    for name, limit in check.LIMITS.items():
        assert read[name] <= limit, (name, read[name], limit)
    assert check.verdict(read)
    # The shards offloaded in the window reached the answers.
    assert rec.acked and rec.q_acked.max() > 0
