"""The comparison that decides ``correct``: every answer due in the window
against the plain reference (``bench/ref.py``), over exactly the records
acknowledged (window queries) or submitted (live-map reads) before it was
served, and the store audited against the configuration's guarantees.

Each number has a limit; PERF.md gives the readings each limit was set
from. A request whose answer came back with ``overflow`` set, or that
raised, is not compared: it counts as failed.

``control`` puts a control in the program's place, read against the
reference at full precision; each must come out not correct:

* ``"bf16"``: the plain reference over the same records rounded to
  bfloat16 (``ref.control_rows``);
* ``"bf16_sums"``: counts, min, max and live reads exact, the sums taken
  over the values rounded to bfloat16 (``ref.control_values``). It fails
  through ``sum_rel_err`` alone.
"""

from __future__ import annotations

import numpy as np

from bench import ref

# Exact comparisons have the limit 0. sum_rel_err: the program's largest
# reading over sound runs lies far below it, the control's smallest far
# above (PERF.md, "How correct is decided").
LIMITS = {
    "count_mismatch": 0,      # queries whose count differs
    "minmax_mismatch": 0,     # (query, channel) min or max that differs
    "sum_rel_err": 5e-5,      # worst |sum - ref| / sum(|v|), per channel
    "latest_mismatch": 0,     # live-map reads that differ anywhere
    "stored_gap": 0,          # |stored tuples - flushed records x replicas|
    "counters_gap": 0,        # |accepted - flushed - pending|
    "index_dropped": 0,       # index entries dropped for capacity
    "ring_wrapped": 0,        # tuples overwritten by ring retention
}


def _window(pre: ref.RecordSet, win_rows, bounds, channels) -> dict:
    a = pre.window(bounds, channels)
    b = ref.RecordSet(win_rows).window(bounds, channels)
    return {"count": a["count"] + b["count"], "sum": a["sum"] + b["sum"],
            "abs_sum": a["abs_sum"] + b["abs_sum"],
            "min": np.fmin(a["min"], b["min"]),
            "max": np.fmax(a["max"], b["max"])}


def _same(a, b) -> np.ndarray:
    return (a == b) | (np.isnan(a) & np.isnan(b))


CONTROLS = {"bf16": ref.control_rows, "bf16_sums": ref.control_values}


def window_readings(sched, rec, channels, control: str | None = None):
    """(count_mismatch, minmax_mismatch, sum_rel_err, compared) over every
    served request that did not fail."""
    w = sched.pre_rows.shape[1]
    r_per = sched.shard_rows.shape[1]
    win_rows = sched.shard_rows[rec.acked].reshape(-1, w)
    pre = ref.RecordSet(sched.pre_rows)
    if control:
        lower = CONTROLS[control]
        pre_c = ref.RecordSet(lower(sched.pre_rows))
        win_c = lower(win_rows)
    cm = mm = compared = 0
    worst = 0.0
    for j in np.nonzero(rec.q_ok)[0]:
        b = {k: v[j] for k, v in sched.query_bounds.items()}
        n = int(rec.q_acked[j]) * r_per
        want = _window(pre, win_rows[:n], b, channels)
        if control:
            got = _window(pre_c, win_c[:n], b, channels)
            if control == "bf16_sums":
                got = dict(want, sum=got["sum"])
        else:
            got = {"count": rec.q_count[j], "sum": rec.q_sum[j],
                   "min": rec.q_min[j], "max": rec.q_max[j]}
        cm += int(np.sum(got["count"] != want["count"]))
        mm += int(np.sum(~_same(got["min"], want["min"]))
                  + np.sum(~_same(got["max"], want["max"])))
        some = want["abs_sum"] > 0
        if some.any():
            err = np.abs(np.asarray(got["sum"], np.float64) - want["sum"])
            worst = max(worst, float(np.max(err[some] / want["abs_sum"][some])))
        compared += 1
    return cm, mm, worst, compared


def latest_readings(sched, rec, n_drones: int, control: bool = False):
    """(latest_mismatch, compared) over every served live-map read."""
    w = sched.pre_rows.shape[1]
    r_per = sched.shard_rows.shape[1]
    stream_drone = np.repeat(sched.shard_drone, r_per)
    stream = sched.shard_rows.reshape(-1, w)

    def latest(rows_of):
        # The preloaded records come first in the stream, so their newest
        # record per drone stands for all of them.
        base, valid = ref.latest_reference(
            sched.pre_drone, rows_of(sched.pre_rows), n_drones)
        seen = np.nonzero(valid)[0]
        rows = rows_of(stream)
        return lambda n: ref.latest_reference(
            np.r_[seen, stream_drone[:n]],
            np.concatenate([base[seen], rows[:n]]), n_drones)

    truth = latest(lambda r: r)
    control_at = latest(ref.control_rows) if control else None
    bad = compared = 0
    for j in np.nonzero(rec.p_ok)[0]:
        n = int(rec.p_submitted[j]) * r_per
        want = truth(n)
        got = control_at(n) if control else (rec.p_record[j], rec.p_valid[j])
        bad += not (np.array_equal(got[1], want[1])
                    and bool(np.all(_same(got[0], want[0]))))
        compared += 1
    return bad, compared


def readings(sched, rec, audit: dict, n_drones: int, channels,
             control: str | None = None) -> dict:
    """Every number compared, by name, plus how many answers were."""
    cm, mm, worst, nq = window_readings(sched, rec, channels, control)
    lm, npoll = latest_readings(sched, rec, n_drones, control == "bf16")
    out = {"count_mismatch": cm, "minmax_mismatch": mm, "sum_rel_err": worst,
           "latest_mismatch": lm, **audit}
    out["compared_requests"] = nq
    out["compared_polls"] = npoll
    return out


def verdict(read: dict) -> bool:
    """Correct when every number is within its limit and answers of both
    kinds were compared."""
    return (all(read[k] <= v for k, v in LIMITS.items())
            and read["compared_requests"] > 0 and read["compared_polls"] > 0)
