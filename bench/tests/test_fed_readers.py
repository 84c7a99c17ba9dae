"""The two readers of what the federation adds to a trace,
``query_exchange_ms`` (the ``query.exchange`` scope) and ``fed_call_ms``
(the ``aerialdb.fed.call`` span): on a four-chip slice counted by hand, and
silent where the names are absent, as in a one-device run or a program that
does not name them."""

import copy
import json
import types
from pathlib import Path

import pytest

from bench import harness, scopes

DATA = Path(__file__).resolve().parent / "data"
READERS = ["query_exchange_ms", "fed_call_ms"]
MODULES = [[100, 600, "jit_fed_query"]]

# Four chips; two window requests, each with its federated call inside the
# facade's dispatch span; ops with the scope the program's HLO gives each.
# Times in ns. Window: [100, 700].
EVENTS = {
    "spans": [[100, 200, "bench.query"], [200, 100, "bench.query.block"],
              [300, 100, "bench.wait"], [400, 200, "bench.query"],
              [600, 100, "bench.flush"]],
    "program_spans": [[120, 60, "aerialdb.query.dispatch"],
                      [122, 8, "aerialdb.fed.check"],
                      [132, 40, "aerialdb.fed.call"],
                      [410, 50, "aerialdb.query.dispatch"],
                      [412, 6, "aerialdb.fed.check"],
                      [420, 30, "aerialdb.fed.call"],
                      [610, 30, "aerialdb.insert"],
                      [612, 4, "aerialdb.fed.check"],
                      [618, 20, "aerialdb.fed.call"]],
    "devices": {
        "/device:TPU:0": {"ops": [[150, 30, "fusion.1", "query.lookup"],
                                  [190, 20, "all-gather.1", "query.exchange"],
                                  [210, 10, "fusion.2", "query.merge"],
                                  [450, 12, "all-gather.1", "query.exchange"],
                                  [650, 9, "all-gather.3",
                                   "insert.exchange"]],
                          "modules": MODULES},
        "/device:TPU:1": {"ops": [[150, 40, "fusion.1", "query.lookup"],
                                  [195, 15, "all-gather.1", "query.exchange"],
                                  [450, 30, "all-gather.1", "query.exchange"]],
                          "modules": MODULES},
        "/device:TPU:2": {"ops": [[160, 20, "all-gather.1", "query.exchange"],
                                  [450, 8, "all-gather.1", "query.exchange"]],
                          "modules": MODULES},
        "/device:TPU:3": {"ops": [[150, 10, "fusion.1", "query.lookup"],
                                  [200, 25, "all-gather.1", "query.exchange"],
                                  [450, 10, "all-gather.1", "query.exchange"],
                                  [470, 40, "fusion.3", "query.scan"]],
                          "modules": MODULES},
    },
}


def _read(events):
    red = scopes.reduce(events)
    run = types.SimpleNamespace(cell="d400-fed4.query_mix_k80", trace=red,
                                scoped=red)
    return {m: harness.load_module("metrics", m).read(run) for m in READERS}


def test_the_two_readers_on_four_chips():
    # query.exchange per chip: request 1 [20, 15, 20, 25], request 2
    # [12, 30, 8, 10]; the longest chip's 25 and 30, median 27.5 ns. The
    # federated call inside each request: 40 and 30 ns, median 35; the
    # insert's call lies in no request.
    assert _read(EVENTS) == pytest.approx({"query_exchange_ms": 27.5e-6,
                                           "fed_call_ms": 35e-6})


def test_silent_without_the_names():
    """The same slice as a program without the federation's names gives
    it: the all-gathers under ``query.merge``, no ``aerialdb.fed.*``
    span."""
    events = copy.deepcopy(EVENTS)
    events["program_spans"] = [s for s in events["program_spans"]
                               if not s[2].startswith("aerialdb.fed.")]
    for dev in events["devices"].values():
        for op in dev["ops"]:
            if op[3] == "query.exchange":
                op[3] = "query.merge"
    assert _read(events) == dict.fromkeys(READERS)


def test_silent_on_a_one_device_run():
    """The slice recorded on one chip in ``d400.query_mix``."""
    rec = json.loads((DATA / "chip_slice_scoped.json").read_text())
    assert _read(rec["events"]) == dict.fromkeys(READERS)
