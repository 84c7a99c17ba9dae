"""The reduction from trace events to what the metrics read, on a slice of
events counted by hand and on a slice recorded on the chip."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

# Two devices, host spans of one window request (with its block nested), a
# wait and a flush, 20 ns outside any span; times in ns. Window: [100, 700].
EVENTS = {
    "spans": [[100, 300, "bench.query"], [250, 150, "bench.query.block"],
              [420, 180, "bench.wait"], [600, 100, "bench.flush"]],
    "devices": {
        "/device:TPU:0": {
            "ops": [[0, 120, "early"], [150, 50, "fusion.1"],
                    [220, 80, "all-gather.2"], [280, 70, "fusion.3"],
                    [650, 40, "fusion.4"]],
            "modules": [[100, 260, "jit_q"], [650, 40, "jit_i"]]},
        "/device:TPU:1": {
            "ops": [[300, 200, "all-reduce.1"]],
            "modules": [[300, 200, "jit_q"]]},
    },
}


@pytest.fixture(scope="module")
def red():
    return trace.reduce(EVENTS)


def test_window_and_busy_union(red):
    assert red.window_ns == 600
    # TPU:0: [100,120] clipped + [150,200] + [220,350] merged + [650,690].
    assert red.busy_ns.tolist() == [240, 200]


def test_device_time_per_host_span(red):
    assert red.span_busy["bench.query"].tolist() == [[200, 100]]
    assert red.span_busy["bench.query.block"].tolist() == [[100, 100]]
    assert red.span_busy["bench.wait"].tolist() == [[0, 80]]
    assert red.span_busy["bench.flush"].tolist() == [[40, 0]]


def test_collective_time_per_host_span(red):
    assert red.span_collective["bench.query"].tolist() == [[80, 100]]
    assert red.span_collective["bench.flush"].tolist() == [[0, 0]]


def test_short_names():
    assert trace.short_name("%fusion.5 = pred[8]{0} fusion(%a)") == "fusion.5"
    assert trace.short_name("jit__query_step_jit(18265631621950313945)") \
        == "jit__query_step_jit"


def test_device_time_per_program_and_op(red):
    assert red.modules == {"jit_q": (2, 460), "jit_i": (1, 40)}
    # Each op is named by the program it ran in.
    assert red.ops == {"jit_q:early": 20, "jit_q:fusion.1": 50,
                       "jit_q:all-gather.2": 80, "jit_q:fusion.3": 70,
                       "jit_i:fusion.4": 40, "jit_q:all-reduce.1": 200}


def test_idle_named_by_innermost_host_span(red):
    # Mean over the two devices of the idle time in each elementary stretch.
    assert red.idle_by_span == pytest.approx({
        "bench.query": 100, "bench.query.block": 50, trace.NO_SPAN: 10,
        "bench.wait": 140, "bench.flush": 80})
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_ns - red.busy_ns.mean())


def test_breakdown_lists_the_largest_first(red):
    b = red.breakdown(top=2)
    assert b["device_ops"] == [["jit_q:all-reduce.1", 100e-9],
                               ["jit_q:all-gather.2", 40e-9]]
    assert [n for n, _ in b["idle_gaps"]] == ["bench.wait", "bench.query"]


def test_recorded_chip_slice():
    """A slice of a traced run of ``d400.query_mix`` on a TPU v5e (events
    as ``trace.load`` gives them), with the readings it gave there."""
    rec = json.loads((DATA / "chip_slice.json").read_text())
    red = trace.reduce(rec["events"])
    want = rec["expect"]
    assert red.window_ns == pytest.approx(want["window_ns"])
    assert red.busy_ns.tolist() == pytest.approx(want["busy_ns"])
    q = red.span_busy["bench.query"].max(axis=1)
    assert np.median(q) == pytest.approx(want["query_device_ns_median"])
    assert red.idle_by_span == pytest.approx(want["idle_by_span"])
    # The query program's device time lies inside its host span.
    assert (q > 0).all()
