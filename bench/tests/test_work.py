"""Required work of a window request and the table of peaks."""

import pytest

from bench import work


def test_query_bytes_counted_by_hand():
    # 2 edges holding 5 and 3 filled slots; each slot is t, lat, lon and
    # V = 2 values as float32 (5 x 4 B) and the shard id as two int32
    # (2 x 4 B): 28 B; 8 slots read once each.
    assert work.slot_bytes(2) == 28
    assert work.query_bytes(5 + 3, 2) == 224
    # At D400's 4 channels a slot is 36 B.
    assert work.slot_bytes(4) == 36
    # 6 range compares per (slot, query): 8 slots x 3 queries x 6.
    assert work.query_compares(8, 3) == 144


def test_least_time_uses_the_chips_bandwidth():
    one = work.least_query_s(1_000_000, 4, 1, "TPU v5 lite")
    assert one == pytest.approx(36e6 / 819e9)
    assert work.least_query_s(1_000_000, 4, 4, "TPU v5 lite") == \
        pytest.approx(one / 4)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.least_query_s(10, 4, 1, "TPU v9 imaginary")
