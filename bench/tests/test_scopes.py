"""The program's own names in a trace (``bench/scopes.py``) and the six
metrics that read them: on a slice counted by hand, on the slice recorded
on the chip before the program had any names, and on one recorded with
them."""

import dataclasses
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
READERS = ["query_lookup_ms", "query_plan_ms", "query_scan_ms",
           "query_prepare_ms", "query_dispatch_ms", "flush_host_ms"]

# Two devices; one window request with its block nested, one flush, a wait;
# the program's spans inside them; ops with the scope the program's HLO
# gives each (None: no scope). Times in ns. Window: [100, 700].
EVENTS = {
    "spans": [[100, 300, "bench.query"], [250, 150, "bench.query.block"],
              [420, 180, "bench.wait"], [600, 100, "bench.flush"]],
    "program_spans": [[105, 40, "aerialdb.make_pred"],
                      [150, 90, "aerialdb.query"],
                      [155, 25, "aerialdb.query.prepare"],
                      [180, 50, "aerialdb.query.dispatch"],
                      [602, 88, "aerialdb.ingest.flush"],
                      [605, 10, "aerialdb.ingest.coalesce"],
                      [615, 15, "aerialdb.ingest.dispatch"],
                      [640, 45, "aerialdb.ingest.block"]],
    "devices": {
        "/device:TPU:0": {
            "ops": [[0, 120, "early", None],
                    [150, 50, "fusion.1", "query.lookup"],
                    [220, 80, "all-gather.2", "query.merge"],
                    [300, 50, "fusion.3", "query.scan"],
                    [650, 40, "fusion.4", "insert.ring"]],
            "modules": [[100, 260, "jit_q"], [650, 40, "jit_i"]]},
        "/device:TPU:1": {
            "ops": [[300, 60, "fusion.1", "query.plan"],
                    [360, 40, "copy.9", None],
                    [400, 100, "fusion.3", "query.scan"]],
            "modules": [[300, 200, "jit_q"]]},
    },
}


def _plain(events):
    """The events as ``trace.load`` gives them: no scope on the ops."""
    return {"spans": events["spans"],
            "devices": {d: {"ops": [o[:3] for o in v["ops"]],
                            "modules": v["modules"]}
                        for d, v in events["devices"].items()}}


@pytest.fixture(scope="module")
def red():
    return scopes.reduce(EVENTS)


def _run(reduced):
    """What a metric reader sees of a traced run, its reduction made."""
    return types.SimpleNamespace(cell="d400.query_mix", trace=reduced,
                                 scoped=reduced)


def test_every_field_of_the_plain_reduction_is_kept(red):
    plain = trace.reduce(_plain(EVENTS))
    for f in dataclasses.fields(trace.Reduced):
        got, want = getattr(red, f.name), getattr(plain, f.name)
        if isinstance(want, dict):
            assert got.keys() == want.keys()
            for k in want:
                np.testing.assert_array_equal(got[k], want[k])
        else:
            np.testing.assert_array_equal(got, want)


def test_device_time_per_scope_in_each_span(red):
    q = red.scope_busy["bench.query"]
    # TPU:0 in [100, 400]: lookup [150,200], merge [220,300], scan
    # [300,350]. TPU:1: plan [300,360], unscoped copy [360,400].
    assert q["query.lookup"].tolist() == [[50, 0]]
    assert q["query.merge"].tolist() == [[80, 0]]
    assert q["query.scan"].tolist() == [[50, 0]]
    assert q["query.plan"].tolist() == [[0, 60]]
    # The early op [100,120] and the copy carry no scope.
    assert q[scopes.UNSCOPED].tolist() == [[20, 40]]
    assert red.scope_busy["bench.flush"]["insert.ring"].tolist() == [[40, 0]]
    assert red.scope_busy["bench.wait"]["query.scan"].tolist() == [[0, 80]]


def test_unscoped_is_what_no_scope_covers(red):
    for name, per in red.scope_busy.items():
        scoped = sum(v for k, v in per.items() if k != scopes.UNSCOPED)
        np.testing.assert_array_equal(scoped + per[scopes.UNSCOPED],
                                      red.span_busy[name])
    assert red.unscoped_share("bench.query") == pytest.approx(60 / 300)


def test_span_times(red):
    assert red.span_times["aerialdb.query"].tolist() == [[150, 240]]
    assert red.span_times["bench.query.block"].tolist() == [[250, 400]]


def test_host_time_inside_spans(red):
    assert red.host_ns("bench.query", ["aerialdb.make_pred",
                                       "aerialdb.query.prepare"]).tolist() \
        == [65]
    assert red.host_ns("aerialdb.ingest.flush",
                       ["aerialdb.ingest.block"]).tolist() == [45]
    assert red.host_ns("bench.wait", ["aerialdb.make_pred"]).tolist() == [0]


def test_idle_named_by_the_innermost_span_of_either_kind(red):
    # Idle time, mean over the two devices, of each elementary stretch.
    assert red.idle_by_innermost == pytest.approx({
        "bench.query": 12.5, "aerialdb.make_pred": 32.5,
        "aerialdb.query": 7.5, "aerialdb.query.prepare": 12.5,
        "aerialdb.query.dispatch": 35, "bench.query.block": 50,
        trace.NO_SPAN: 10, "bench.wait": 140, "bench.flush": 12,
        "aerialdb.ingest.flush": 15.5, "aerialdb.ingest.coalesce": 10,
        "aerialdb.ingest.dispatch": 15, "aerialdb.ingest.block": 27.5})
    assert sum(red.idle_by_innermost.values()) == pytest.approx(
        sum(red.idle_by_span.values()))


def test_breakdown_names_program_scope_and_op(red):
    b = red.breakdown(top=3)
    assert b["device_ops"] == [["jit_q:query.scan/fusion.3", 75e-9],
                               ["jit_q:query.merge/all-gather.2", 40e-9],
                               ["jit_q:query.plan/fusion.1", 30e-9]]
    assert red.scoped_ops["jit_q:early"] == 20
    assert [n for n, _ in b["idle_gaps"]] == ["bench.wait",
                                              "bench.query.block",
                                              "aerialdb.query.dispatch"]


def test_the_six_readers(red):
    run = _run(red)
    got = {m: harness.load_module("metrics", m).read(run) for m in READERS}
    assert got == pytest.approx({
        # Longest chip per request: TPU:0 holds lookup + merge (130 ns)
        # and scan (50); TPU:1 plan (60). The flush: 88 ns less its 45 ns
        # block.
        "query_lookup_ms": 130e-6, "query_plan_ms": 60e-6,
        "query_scan_ms": 50e-6,
        "query_prepare_ms": 65e-6, "query_dispatch_ms": 50e-6,
        "flush_host_ms": 43e-6})


def test_innermost_scope():
    assert scopes.innermost_scope(
        "jit(fed_query)/shard_map/query.lookup/vmap()/sort") == "query.lookup"
    assert scopes.innermost_scope("jit(f)/query.plan/query.orlist/x") \
        == "query.orlist"
    assert scopes.innermost_scope("jit(f)/while/body/add") is None


HLO = """\
fused_computation.13 {
  param_0.1 = s32[8]{0} parameter(0)
  reshape.2 = s32[8]{0} reshape(param_0.1), metadata={op_name="jit(q)/query.orlist/broadcast_in_dim" stack_frame_id=7}
  ROOT scatter.6 = s32[8]{0} scatter(param_0.1, reshape.2, reshape.2), to_apply=region_1.2
}

region_1.2 {
  a.1 = s32[] parameter(0), metadata={op_name="scatter"}
  ROOT b.1 = s32[] parameter(1)
}

body.5 {
  p.1 = s32[8]{0} parameter(0)
  ROOT add.3 = s32[8]{0} add(p.1, p.1), metadata={op_name="jit(q)/query.plan/while/body/add"}
}

ENTRY main.9 {
  p.0 = s32[8]{0} parameter(0), metadata={op_name="pred.t0"}
  fusion.13 = s32[8]{0} fusion(p.0), kind=kCustom, calls=fused_computation.13
  while.1 = s32[8]{0} while(fusion.13), condition=body.5, body=body.5
  ROOT copy.4 = s32[8]{0} copy(while.1)
}
"""


def test_scopes_from_program_text():
    got = scopes.hlo_scopes(HLO)
    # A fusion with no metadata of its own takes its fused instructions'.
    assert got["fusion.13"] == "query.orlist"
    assert got["while.1"] == "query.plan"
    assert got["reshape.2"] == "query.orlist"
    assert got["p.0"] is None and got["copy.4"] is None


def test_a_trace_without_the_programs_names():
    """The slice recorded before the program had spans or scopes (the
    parent of this reduction): every field as before, every reader
    silent."""
    rec = json.loads((DATA / "chip_slice.json").read_text())
    red = scopes.reduce(rec["events"])
    assert red.window_ns == pytest.approx(rec["expect"]["window_ns"])
    assert red.idle_by_innermost == pytest.approx(red.idle_by_span)
    assert not red.has_scopes("bench.query")
    assert red.unscoped_share("bench.query") == pytest.approx(1.0)
    run = _run(red)
    assert {m: harness.load_module("metrics", m).read(run)
            for m in READERS} == dict.fromkeys(READERS)


def test_recorded_chip_slice_with_scopes():
    """Three window requests and a flush of a traced ``d400.query_mix`` run
    on a TPU v5e, with the readings they gave there."""
    rec = json.loads((DATA / "chip_slice_scoped.json").read_text())
    red = scopes.reduce(rec["events"])
    want = rec["expect"]
    assert red.window_ns == pytest.approx(want["window_ns"])
    assert red.busy_ns.tolist() == pytest.approx(want["busy_ns"])
    per = red.scope_busy["bench.query"]
    assert {k: v.max(axis=1).tolist() for k, v in per.items()} == \
        pytest.approx(want["scope_ns"])
    assert red.host_ns("bench.query", ["aerialdb.make_pred",
                                       "aerialdb.query.prepare"]).tolist() \
        == pytest.approx(want["prepare_ns"])
    assert red.idle_by_innermost == pytest.approx(want["idle_by_innermost"])
    # The scopes cover at least 95 % of the requests' device time.
    assert red.unscoped_share("bench.query") < 0.05
    scoped = sum(v for k, v in per.items() if k != scopes.UNSCOPED)
    query = red.span_busy["bench.query"]
    assert scoped.sum() >= 0.95 * query.sum()
