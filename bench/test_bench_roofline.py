"""The least-time function against hand counts, and the peaks table."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import forestgen  # noqa: E402
import roofline  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")


def tiny(bits, d=784):
    # two trees of 3 leaves, each with a leaf at depth 2
    return roofline.work(np.array([2, 2]), np.array([3, 3]),
                         np.array([2, 2]), d, 10, bits)


def test_bytes_and_compares_by_hand():
    f = tiny(None)
    # 4 nodes × (f32 threshold + 2-byte feature id) + 6 leaves × 10 × 4
    assert f.model_bytes == 4 * (4 + 2) + 6 * 10 * 4 == 264
    assert f.row_bytes == 784 * 4 + 10 * 4 == 3176
    assert f.compares_per_row == 2
    q = tiny(16)
    assert q.model_bytes == 4 * (2 + 2) + 6 * 10 * 2 == 136
    assert q.row_bytes == 784 * 2 + 10 * 4
    assert tiny(None, d=136).model_bytes == 4 * (4 + 1) + 240


def test_least_time_takes_the_larger_term():
    t, bound = roofline.least_time(tiny(None), 100, V5E)
    assert bound == "bytes"
    assert t == pytest.approx((264 + 100 * 3176) / 819e9)
    w = roofline.Work(model_bytes=0, row_bytes=0, compares_per_row=10 ** 9,
                      integer=True)
    t, bound = roofline.least_time(w, 1, V5E)
    assert bound == "compares"
    assert t == pytest.approx(1e9 / 393e12)
    w = roofline.Work(0, 0, 10 ** 9, integer=False)
    assert roofline.least_time(w, 1, V5E)[0] == pytest.approx(1e9 / 197e12)


def test_work_of_generated_forest_matches_its_shape():
    tr = forestgen.grow(forestgen.rng_for(1, 0), 50, 16, 20, 1, "normal")
    w = roofline.work(tr.n_nodes, tr.n_leaves, tr.shallowest, 20, 1, None)
    assert w.model_bytes == 50 * 15 * (4 + 1) + 50 * 16 * 4
    assert w.compares_per_row == int((tr.shallowest - 1).sum())
    assert 50 <= w.compares_per_row < 50 * 15


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v4")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
