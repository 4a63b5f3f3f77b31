"""Peaks and the least-work arithmetic of the roofline metrics."""

import types

import numpy as np
import pytest

from bench import roofline


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.least_time_s(1.0, 1.0, "TPU v9 imaginary")


def test_v5e_peaks_and_bound():
    p = roofline.peaks("TPU v5 lite")
    assert p["flop_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    # memory-bound: 819 bytes take a nanosecond, two flops take far less
    assert roofline.least_time_s(2, 819, "TPU v5 lite") == pytest.approx(1e-9)
    assert roofline.least_time_s(197e3, 0, "TPU v5 lite") == \
        pytest.approx(1e-9)


def test_byte_counts_of_a_tiny_plan_match_a_hand_count():
    # 3 owners; blocks {0,1}, {1,2}; owner links 0->b0, 1->b0, 1->b1, 2->b1
    index = types.SimpleNamespace(
        n=3, num_blocks=2,
        block_members=np.array([0, 1, 1, 2], np.int32),
        link_block=np.array([0, 0, 1, 1], np.int32))
    c = roofline.plan_counts(index)
    assert c == {"kind": "dbindex", "n": 3, "members": 4, "blocks": 2,
                 "links": 4, "index_words": 8}
    # pass 1, one channel: 4 values + 4 ids read, 2 block sums written
    assert roofline.segment_sum_work(4, 1, 2) == (4, 4 * (4 + 4 + 2))
    # pass 2, two channels: 8 values + 4 ids read, 6 owner sums written
    assert roofline.segment_sum_work(4, 2, 3) == (8, 4 * (8 + 4 + 6))
    # an [8, n] launch of 4 channels: 8 index words, 8 x 4 member values,
    # 8 x 3 x 4 outputs, 4 bytes each
    assert roofline.launch_bytes(8, 4, 3, 8, 4) == 4 * (8 + 32 + 96)
    iidx = types.SimpleNamespace(n=3, wd_members=np.arange(5, dtype=np.int32))
    assert roofline.plan_counts(iidx) == {"kind": "iindex", "n": 3,
                                          "members": 5, "index_words": 11}
