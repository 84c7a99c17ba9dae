"""The comparison that decides ``correct`` has to fail the control and every
fault the cells can have, on a tiny run on the CPU.

* The controls, in the program's place: the plain reference over the same
  records in bfloat16, one precision step below the float32 the store
  keeps; and the reference with only its sums taken over bfloat16 values.
* Faults, planted in the program underneath the harness, which otherwise
  runs as on the chip: an insert that returns the state unchanged; half of
  an insert batch left out; the candidate exchange between chips left out;
  an answer altered where it is produced (a window count, a live-map row).
"""

import time

import jax
import numpy as np
import pytest

from bench import check, harness
from repro.api import AerialDB
from repro.core import datastore as ds
from repro.distributed import federation as fed
from repro.ingest import IngestPipeline

SEED = 2**31 + 777


def _run(tiny, tmp_path, fed4=False):
    cell, config = (("d400-fed4.query_mix", "d400-fed4") if fed4
                    else ("d400.query_mix", "d400"))
    return harness.execute(cell, SEED, 3.0, False, tmp_path, time.time(),
                           require_tpu=False, config=tiny(config),
                           log=lambda line: None)


def _readings(run, audit, control=None):
    return check.readings(run.schedule, run.records, audit,
                          run.config["fleet"]["n_drones"],
                          tuple(run.traffic["queries"]["channels"]), control)


def test_program_correct_and_control_not(tiny, tmp_path):
    run, audit = _run(tiny, tmp_path)
    program = _readings(run, audit)
    control = _readings(run, audit, control="bf16")
    assert check.verdict(program)
    assert not check.verdict(control)
    assert control["count_mismatch"] > 0
    assert control["minmax_mismatch"] > 0
    assert control["latest_mismatch"] > 0
    assert control["sum_rel_err"] > 10 * check.LIMITS["sum_rel_err"]


def test_bf16_sums_fail_through_the_sum_limit_alone(tiny, tmp_path):
    run, audit = _run(tiny, tmp_path)
    sums = _readings(run, audit, control="bf16_sums")
    assert not check.verdict(sums)
    assert sums["sum_rel_err"] > check.LIMITS["sum_rel_err"]
    assert all(sums[k] <= v for k, v in check.LIMITS.items()
               if k != "sum_rel_err")


def _state_unchanged(monkeypatch):
    real = ds._insert

    def insert(cfg, state, payload, meta, alive):
        return state, real(cfg, state, payload, meta, alive)[1]
    monkeypatch.setattr(ds, "_insert", insert)


def _half_batch(monkeypatch):
    real = ds._insert

    def insert(cfg, state, payload, meta, alive):
        half = payload.shape[0] // 2
        if half == 0:
            return state, real(cfg, state, payload, meta, alive)[1]
        return real(cfg, state, payload[:half],
                    type(meta)(*(f[:half] for f in meta)), alive)
    monkeypatch.setattr(ds, "_insert", insert)


def _count_altered(monkeypatch):
    real = AerialDB.query

    def query(self, *a, **kw):
        res, info = real(self, *a, **kw)
        return res._replace(count=res.count.at[0].add(1)), info
    monkeypatch.setattr(AerialDB, "query", query)


def _latest_altered(monkeypatch):
    real = IngestPipeline.latest

    def latest(self):
        record, valid = real(self)
        record[0, 1] += np.float32(1e-3)
        return record, valid
    monkeypatch.setattr(IngestPipeline, "latest", latest)


def _exchange_left_out(monkeypatch):
    monkeypatch.setattr(fed, "_merge_matched",
                        lambda local, max_shards, axes: local)


@pytest.fixture
def fresh_programs():
    """Every jitted program traced anew, before and after the fault."""
    def clear():
        for fn in (fed._query_fn, fed._insert_fn, fed._ingest_fn):
            fn.cache_clear()
        jax.clear_caches()
    clear()
    yield
    clear()


@pytest.mark.parametrize("fault,fed4", [
    (_state_unchanged, False), (_half_batch, False), (_count_altered, False),
    (_latest_altered, False), (_exchange_left_out, True)])
def test_fault_is_not_correct(fault, fed4, tiny, tmp_path, monkeypatch,
                              fresh_programs):
    fault(monkeypatch)
    run, audit = _run(tiny, tmp_path, fed4)
    read = _readings(run, audit)
    assert not check.verdict(read)
