"""Open-loop traffic over a drone fleet: window queries, live ingest, flush
ticks and live-map polls, each on its own schedule.

Traffic keys read here (see ``bench/traffic/*.json``):

* ``queries``: ``batch`` queries per request, every query of a request of
  one class drawn from ``windows_s`` x ``boxes_km``, aggregating
  ``channels``. A ``fresh_share`` of the requests, drawn from the seed,
  centre their queries on the last samples of the ``batch`` newest shards
  due at least ``fresh_lag_s`` before the request, so that every run asks
  for data that arrived in the window; the others on flown samples of the
  preload. Requests arrive at the cell's
  ``query_rate_per_s`` by ``schedule.poisson_pattern``, turned by an
  offset drawn from the seed: a whole number of the period in which
  offloads and flush ticks repeat. Each class serves
  the same number of requests, give or take one.
* ``ingest``: every drone of the configuration's fleet offloads one shard
  per ``fleet.shard_period_s`` of the mission clock, which runs the
  configuration's ``mission_clock_speedup`` times real time (1: the
  source's own rate);
  ``offload_offsets: "even"`` spreads the drones' offsets evenly over the
  period, in an order drawn from the seed. The preload is every shard due
  before the window: ``preload_rounds`` whole rounds.
* ``flush_tick_s``: the serving loop's flush tick; ticks go on past the
  window until every shard offloaded in it is flushed.
* ``latest_polls``: ``rate_per_s`` live-map polls by ``poisson_pattern``,
  turned by the same offset.
* ``pattern_seed``: fixes the order of the arrival gaps for every run.
"""

from __future__ import annotations

import itertools
import math
from fractions import Fraction

import numpy as np

from bench.data import fleet_rounds, window_bounds
from bench.schedule import Schedule, poisson_pattern


def build(config: dict, traffic: dict, cell: dict, seed: int,
          seconds: float) -> Schedule:
    fleet = config["fleet"]
    store = config["store"]
    d, r = fleet["n_drones"], store["records_per_shard"]
    period = fleet["shard_period_s"] / config["mission_clock_speedup"]
    n_pre = config["preload_rounds"]
    if traffic["ingest"]["offload_offsets"] != "even":
        raise ValueError(f"unknown offload_offsets "
                         f"{traffic['ingest']['offload_offsets']!r}")
    n_stream = math.ceil(seconds / period) + 1
    rows = fleet_rounds(d, n_pre + n_stream, r, store["n_values"],
                        fleet["sample_period_s"], seed)       # (N, D, R, W)
    n_rounds, _, _, w = rows.shape
    seq = (np.arange(n_rounds)[:, None, None] * r
           + np.arange(r)[None, None, :]).repeat(d, 1)        # (N, D, R)
    drone = np.broadcast_to(np.arange(d)[None, :, None], seq.shape)

    pre = slice(0, n_pre)
    pre_rows = rows[pre].reshape(-1, w)

    # Drone i offloads round n_pre + k at k periods plus its offset.
    rank = np.random.default_rng([seed, 1]).permutation(d)
    due = (np.arange(n_stream)[:, None] + rank[None, :] / d) * period
    keep = due < seconds
    order = np.argsort(due[keep], kind="stable")
    rk, dk = np.nonzero(keep)
    rk, dk = rk[order] + n_pre, dk[order]

    tick = float(traffic["flush_tick_s"])
    last = float(due[keep].max()) if keep.any() else 0.0
    n_ticks = max(math.ceil(seconds / tick), math.ceil(last / tick) + 1)
    ticks = np.arange(n_ticks) * tick

    # One offset turns the request and poll patterns together, by a whole
    # number of the period in which offloads and ticks repeat: every run
    # meets the same joint pattern, only turned round the window.
    cycle = _common_period(period / d, tick)
    offset = cycle * np.random.default_rng([seed, 3]).integers(
        max(int(seconds // cycle), 1))
    q = traffic["queries"]
    q_due = poisson_pattern(cell["query_rate_per_s"], seconds,
                            traffic["pattern_seed"], offset)
    classes = list(itertools.product(q["windows_s"], q["boxes_km"]))
    rng = np.random.default_rng([seed, 2])
    cls = rng.permutation(np.arange(len(q_due)) % len(classes))
    anchors = pre_rows[rng.integers(0, len(pre_rows),
                                    (len(q_due), q["batch"])), :3]
    # Fresh requests: anchored on the newest shards, the preload's last
    # round (due before the window, in the same offset order) then the
    # window's, each shard by its last sample.
    last_pre = rows[n_pre - 1, :, -1, :3]                       # (D, 3)
    pre_order = np.argsort(rank, kind="stable")
    newest_due = np.r_[(rank[pre_order] / d - 1) * period, due[keep][order]]
    newest_rows = np.concatenate([last_pre[pre_order],
                                  rows[rk, dk, -1, :3]])
    fresh = np.full((len(q_due), q["batch"]), -1)
    n_fresh = int(round(q["fresh_share"] * len(q_due)))
    for i in np.sort(rng.permutation(len(q_due))[:n_fresh]):
        upto = np.searchsorted(newest_due, q_due[i] - q["fresh_lag_s"],
                               side="right")
        pick = np.maximum(np.arange(upto - q["batch"], upto), 0)
        anchors[i] = newest_rows[pick]
        fresh[i] = pick - d             # window shard index; < 0: preload
    bounds = {k: np.empty((len(q_due), q["batch"]), np.float32)
              for k in ("lat0", "lat1", "lon0", "lon1", "t0", "t1")}
    for i, c in enumerate(cls):
        window_s, box_km = classes[c]
        for k, v in window_bounds(anchors[i], box_km, window_s).items():
            bounds[k][i] = v

    polls = traffic["latest_polls"]
    p_due = poisson_pattern(polls["rate_per_s"], seconds,
                            traffic["pattern_seed"] + 1, offset)

    return Schedule(
        pre_drone=drone[pre].reshape(-1), pre_seq=seq[pre].reshape(-1),
        pre_rows=pre_rows,
        shard_due=due[keep][order], shard_drone=dk,
        shard_seq=seq[rk, dk], shard_rows=rows[rk, dk],
        tick_due=ticks, query_due=q_due,
        query_bounds=bounds, query_fresh=fresh, poll_due=p_due,
        seconds=float(seconds))


def _common_period(a: float, b: float) -> float:
    """Least common multiple of two periods, as exact fractions."""
    fa = Fraction(a).limit_denominator(10**6)
    fb = Fraction(b).limit_denominator(10**6)
    den = math.lcm(fa.denominator, fb.denominator)
    return math.lcm(int(fa * den), int(fb * den)) / den
