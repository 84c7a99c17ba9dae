"""Fleet telemetry, edge sites and query windows for the benchmark, made
from a seed.

A copy, kept with the benchmark so that no later change to the program can
change what the benchmark feeds it, of three pieces of the program's own
workload code (``repro.data.synthetic`` and ``benchmarks.common``):

* ``make_sites``: edge-server locations drawn uniformly over the city box
  (the paper samples OpenCellID towers);
* ``fleet_rounds``: the paper's drone random walk (sec 4.4.1: hover with
  P=0.8, else a step of ~10 m/s), one 60-sample shard per drone per round,
  samples every 5 s;
* ``window_bounds``: the sec 4.5.1 windows, {5 min, 30 min, 2 h} x
  {200 m, 1 km, 5 km}, centred on flown (t, lat, lon) samples.
"""

from __future__ import annotations

import numpy as np

# ~Bangalore, the paper's city: about 27 km x 33 km.
LAT_MIN, LAT_MAX = 12.85, 13.10
LON_MIN, LON_MAX = 77.45, 77.75
P_HOVER = 0.8
SPEED_DEG = 0.0001          # ~11 m per 1 s step at these latitudes
KM_PER_DEG = 111.0


def make_sites(n_edges: int, seed: int) -> np.ndarray:
    """(E, 2) float32 edge-server (lat, lon)."""
    rng = np.random.default_rng(seed)
    lat = rng.uniform(LAT_MIN, LAT_MAX, n_edges)
    lon = rng.uniform(LON_MIN, LON_MAX, n_edges)
    return np.stack([lat, lon], axis=1).astype(np.float32)


def fleet_rounds(n_drones: int, n_rounds: int, records_per_shard: int,
                 n_values: int, sample_period_s: float,
                 seed: int) -> np.ndarray:
    """(N, D, R, 3+V) float32: N collection rounds of D drones, each round
    one R-sample shard per drone; columns t, lat, lon, values. Sample k of
    round r is taken at ``(r * R + k) * sample_period_s`` by every drone."""
    rng = np.random.default_rng(seed)
    d, r, v = n_drones, records_per_shard, n_values
    pos = np.stack([rng.uniform(LAT_MIN, LAT_MAX, d),
                    rng.uniform(LON_MIN, LON_MAX, d)], axis=1)
    out = np.empty((n_rounds, d, r, 3 + v), np.float32)
    for n in range(n_rounds):
        for k in range(r):
            hover = rng.random(d) < P_HOVER
            step = rng.normal(0, SPEED_DEG * sample_period_s, (d, 2))
            pos = np.where(hover[:, None], pos, pos + step)
            pos[:, 0] = np.clip(pos[:, 0], LAT_MIN, LAT_MAX)
            pos[:, 1] = np.clip(pos[:, 1], LON_MIN, LON_MAX)
            out[n, :, k, 0] = (n * r + k) * sample_period_s
            out[n, :, k, 1] = pos[:, 0]
            out[n, :, k, 2] = pos[:, 1]
        out[n, :, :, 3:] = rng.normal(25.0, 5.0, (d, r, v))
    return out


def window_bounds(anchors: np.ndarray, box_km: float,
                  window_s: float) -> dict:
    """Bounds of AND windows centred on ``anchors`` ((Q, 3) t, lat, lon):
    a ``box_km`` square and ``window_s`` seconds, each as (Q,) float32."""
    deg = box_km / KM_PER_DEG
    return dict(
        lat0=(anchors[:, 1] - deg / 2).astype(np.float32),
        lat1=(anchors[:, 1] + deg / 2).astype(np.float32),
        lon0=(anchors[:, 2] - deg / 2).astype(np.float32),
        lon1=(anchors[:, 2] + deg / 2).astype(np.float32),
        t0=(anchors[:, 0] - window_s / 2).astype(np.float32),
        t1=(anchors[:, 0] + window_s / 2).astype(np.float32))
