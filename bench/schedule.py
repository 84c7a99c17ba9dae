"""What a traffic generator hands the serving loop: the data to preload and
every event of the window, with its due time, all made in set-up."""

from __future__ import annotations

import dataclasses

import numpy as np

# Event kinds, in the order the loop serves events due at the same instant.
SUBMIT, TICK, QUERY, POLL = 0, 1, 2, 3
KIND_NAMES = ("submit", "tick", "query", "poll")


@dataclasses.dataclass
class Schedule:
    """Everything one run sends, made from the seed before the window.

    pre_drone / pre_seq / pre_rows: (N,) (N,) (N, 3+V) records loaded
        before the window, in submit order.
    shard_due / shard_drone / shard_seq / shard_rows: (M,) (M,) (M, R)
        (M, R, 3+V) the shards offloaded in the window, in due order.
    tick_due: (T,) flush ticks.
    query_due: (Qn,) requests; query_bounds: dict of (Qn, B) float32
        window bounds (lat0, lat1, lon0, lon1, t0, t1); query_fresh: (Qn, B)
        the window shard each query is centred on, or < 0 (a preloaded
        sample).
    poll_due: (P,) live-map polls.
    due / kind / index: (n,) every event in serving order; ``index`` is
        the event's position within its kind.
    seconds: the measured window; events of kind TICK may fall after it,
        to flush what was offloaded inside it.
    """
    pre_drone: np.ndarray
    pre_seq: np.ndarray
    pre_rows: np.ndarray
    shard_due: np.ndarray
    shard_drone: np.ndarray
    shard_seq: np.ndarray
    shard_rows: np.ndarray
    tick_due: np.ndarray
    query_due: np.ndarray
    query_bounds: dict
    query_fresh: np.ndarray
    poll_due: np.ndarray
    seconds: float
    due: np.ndarray = None
    kind: np.ndarray = None
    index: np.ndarray = None

    def __post_init__(self):
        dues = [self.shard_due, self.tick_due, self.query_due, self.poll_due]
        due = np.concatenate(dues)
        kind = np.concatenate([np.full(len(d), k) for k, d in enumerate(dues)])
        index = np.concatenate([np.arange(len(d)) for d in dues])
        order = np.lexsort((index, kind, due))
        self.due, self.kind, self.index = due[order], kind[order], index[order]


def poisson_pattern(rate: float, seconds: float, pattern_seed: int,
                    offset: float) -> np.ndarray:
    """Sorted arrival times in [0, seconds) of ``round(rate * seconds)``
    events whose gaps are the exponential distribution's quantiles at
    ``(i + 0.5) / n``, in an order fixed by ``pattern_seed``, the whole
    pattern turned round the window by ``offset`` seconds.

    Every run thus sends the same Poisson-like pattern of bursts, and the
    run's seed, through ``offset``, moves where in the window they fall.
    Patterns of several event kinds turned by one offset keep their
    alignment to each other."""
    n = int(round(rate * seconds))
    if n == 0:
        return np.empty(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[np.random.default_rng(pattern_seed).permutation(n)]
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    return np.sort((t + offset) % seconds)
