"""Plain numpy reference of what the served path answers, and the control.

Imports nothing of the program. Each function answers from an explicit
record set: the benchmark passes exactly the records acknowledged (for a
window query) or submitted (for a live-map read) before the request.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


class RecordSet:
    """Records ((N, 3+V) t, lat, lon, values) sorted by time once, so each
    window reads only the records inside its time range."""

    def __init__(self, rows: np.ndarray):
        self.rows = rows[np.argsort(rows[:, 0], kind="stable")]
        self.t = self.rows[:, 0]

    def window(self, bounds: dict, channels=(0, 1, 2, 3)) -> dict:
        """Answer of a batch of AND spatio-temporal windows (bounds
        inclusive, as (Q,) arrays): count, float64 sum, float32 min and max
        of each channel (NaN where nothing matches), and the float64 sum
        of absolute values, the scale of any rounding in a sum."""
        q, k = len(bounds["t0"]), len(channels)
        cols = [3 + c for c in channels]
        out = {"count": np.zeros(q, np.int64),
               "sum": np.zeros((q, k)), "abs_sum": np.zeros((q, k)),
               "min": np.full((q, k), np.nan, np.float32),
               "max": np.full((q, k), np.nan, np.float32)}
        for i in range(q):
            lo = np.searchsorted(self.t, bounds["t0"][i], side="left")
            hi = np.searchsorted(self.t, bounds["t1"][i], side="right")
            sub = self.rows[lo:hi]
            m = ((bounds["lat0"][i] <= sub[:, 1]) & (sub[:, 1] <= bounds["lat1"][i])
                 & (bounds["lon0"][i] <= sub[:, 2])
                 & (sub[:, 2] <= bounds["lon1"][i]))
            vals = sub[m][:, cols]
            out["count"][i] = len(vals)
            if len(vals):
                v64 = vals.astype(np.float64)
                out["sum"][i] = v64.sum(0)
                out["abs_sum"][i] = np.abs(v64).sum(0)
                out["min"][i] = vals.min(0)
                out["max"][i] = vals.max(0)
        return out


def latest_reference(drone: np.ndarray, rows: np.ndarray, n_drones: int):
    """Newest record per drone over ``rows`` in stream order: the one of
    largest t, the later in the stream on a tie. Returns ``(record (D, W)
    float32, valid (D,) bool)``, zeros where a drone sent nothing."""
    record = np.zeros((n_drones, rows.shape[1]), np.float32)
    valid = np.zeros(n_drones, bool)
    if len(drone):
        order = np.lexsort((np.arange(len(drone)), rows[:, 0], drone))
        last = np.r_[np.nonzero(np.diff(drone[order]))[0], len(order) - 1]
        pick = order[last]
        record[drone[pick]] = rows[pick]
        valid[drone[pick]] = True
    return record, valid


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def control_rows(rows: np.ndarray) -> np.ndarray:
    """The control: every stored field one precision step down, float32 to
    bfloat16, as a store that kept its log in bfloat16 would answer."""
    return _bf16(rows)


def control_values(rows: np.ndarray) -> np.ndarray:
    """The sums' control: the aggregated values alone in bfloat16, time and
    position kept in float32, as a program that summed bfloat16 values (a
    mask times values on the matrix unit) and kept counts, min and max
    exact would answer."""
    out = rows.copy()
    out[:, 3:] = _bf16(rows[:, 3:])
    return out
