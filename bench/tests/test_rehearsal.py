"""CPU rehearsal of a whole run: the serving loop, the check and the result
line, for each cell at a tiny size, and ``bench/run.py``'s refusals."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
SEED = 2**31 + 12345          # larger than 32 signed bits hold
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell,config", [("d400.query_mix", "d400"),
                                         ("d400-fed4.query_mix", "d400-fed4")])
def test_one_run_of_the_cell(cell, config, tiny, tmp_path):
    lines = []
    result = harness.run_cell(cell, SEED, 3.0, False, tmp_path, time.time(),
                              require_tpu=False, config=tiny(config),
                              log=lines.append)
    json.dumps(result)                          # the line is plain JSON
    assert list(result) == KEYS
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(
        harness.cell_metrics(cell, "end_to_end"))
    assert result["device"]["count"] == tiny(config)["chips"]
    assert all(v["value"] <= v["limit"] for v in result["checks"].values()
               if v["limit"] is not None)
    phases = {p["phase"]: p for p in map(json.loads, lines)}
    window = phases["window"]
    assert window["compiles_in_window"] == 0
    assert phases["work"]["bytes_per_request"] == 36 * phases["work"][
        "filled_slots"]
    assert set(window["generator_late_ms"]) == {"p50", "p99", "max"}


def test_same_seed_same_traffic(tiny):
    from bench.gen import open_loop
    cfg = tiny("d400")
    cell = harness.load_json("cells", "d400.query_mix")
    traffic = harness.load_json("traffic", "query_mix")
    a = open_loop.build(cfg, traffic, cell, SEED, 30.0)
    b = open_loop.build(cfg, traffic, cell, SEED, 30.0)
    c = open_loop.build(cfg, traffic, cell, SEED + 1, 30.0)
    assert (a.due == b.due).all() and (a.pre_rows == b.pre_rows).all()
    assert (a.query_bounds["t0"] == b.query_bounds["t0"]).all()
    # Another seed: other data, the same arrival pattern turned round
    # the window.
    assert not (a.pre_rows == c.pre_rows).all()

    def gaps(s):            # round the window, the last gap wrapping
        d = s.query_due
        return np.sort(np.r_[np.diff(d), 30.0 - d[-1] + d[0]])
    assert np.allclose(gaps(a), gaps(c))
    assert not np.allclose(a.query_due, c.query_due)
    # Turned by a whole number of 3 s, the period in which offloads
    # (every 0.1875 s) and flush ticks (every 1 s) repeat.
    assert any(np.allclose(np.sort((a.query_due + 3.0 * k) % 30.0),
                           c.query_due) for k in range(10))


def test_fresh_requests_ask_about_the_newest_shards(tiny):
    from bench.gen import open_loop
    cfg = tiny("d400")
    cell = harness.load_json("cells", "d400.query_mix")
    traffic = harness.load_json("traffic", "query_mix")
    s = open_loop.build(cfg, traffic, cell, SEED, 30.0)
    q = traffic["queries"]
    # Requests early in the window centre some queries on the preload's
    # last round; the rest of the fresh share only on window shards.
    n_fresh = round(q["fresh_share"] * len(s.query_due))
    on_window = (s.query_fresh >= 0).any(1)
    assert 0 < on_window.sum() <= n_fresh
    assert (s.query_fresh >= 0).all(1)[-len(s.query_due) // 2:].sum() > 0
    for i in np.nonzero(on_window)[0]:
        m = s.query_fresh[i][s.query_fresh[i] >= 0]
        # The newest shards due fresh_lag_s or more before the request,
        # each query centred on its shard's last sample.
        assert s.shard_due[m].max() <= s.query_due[i] - q["fresh_lag_s"]
        if m.max() + 1 < len(s.shard_due):
            assert (s.shard_due[m.max() + 1]
                    > s.query_due[i] - q["fresh_lag_s"])
        centre = (s.query_bounds["t0"][i] + s.query_bounds["t1"][i]) / 2
        assert np.allclose(centre[s.query_fresh[i] >= 0],
                           s.shard_rows[m, -1, 0])


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "d400.query_mix", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_run_refuses_a_process_without_a_tpu():
    p = _run(ARGS, ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(ARGS, tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
