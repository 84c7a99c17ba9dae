"""Work a request requires, whatever implements it, and the chip's peaks.

One window request is one pass that answers its whole batch: it has to
read every filled slot of every edge's log once, each slot holding t, lat,
lon and the V values as float32 and the owning shard id as two int32. The
published peaks give no rate for the vector unit's compares, so the least
time of a request is bound by bytes over HBM bandwidth; its compares are
counted all the same, 6 range compares per (slot, query).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
COMPARES_PER_SLOT_QUERY = 6


def slot_bytes(n_values: int) -> int:
    return (3 + n_values) * 4 + 2 * 4


def query_bytes(filled_slots: int, n_values: int) -> int:
    """Bytes one request must read: every filled slot once."""
    return int(filled_slots) * slot_bytes(n_values)


def query_compares(filled_slots: int, n_queries: int) -> int:
    return int(filled_slots) * n_queries * COMPARES_PER_SLOT_QUERY


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def least_query_s(filled_slots: int, n_values: int, chips: int,
                  device_kind: str) -> float:
    """Least time of one request: its bytes over the HBM bandwidth of the
    chips that hold the log."""
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    return query_bytes(filled_slots, n_values) / (chips * bw)
