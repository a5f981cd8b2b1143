"""Trace reduction: interval arithmetic by hand, and on a small profiler
trace recorded on the CPU (``testdata/cpu_window.xplane.pb``: three
``bench.predict`` spans of a jitted matmul and three ``bench.wait``
sleeps inside one ``bench.window``)."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import devtrace  # noqa: E402

RECORDED = os.path.join(HERE, "testdata", "cpu_window.xplane.pb")


def test_union_busy_gaps_by_hand():
    ops = [(1.0, 2.0, "a"), (1.5, 3.0, "b"), (5.0, 6.0, "a"),
           (9.0, 12.0, "c")]
    w = (0.0, 10.0)
    assert devtrace.union(ops) == [(1.0, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert devtrace.busy(ops, w) == pytest.approx(4.0)
    assert devtrace.gaps(ops, w) == [(0.0, 1.0), (3.0, 5.0), (6.0, 9.0)]
    assert devtrace.op_totals(ops, w) == [["a", 2.0], ["b", 1.5],
                                          ["c", 1.0]]


def test_idle_gaps_labelled_by_span_covering_most():
    ops = [(1.0, 2.0, "k"), (4.0, 5.0, "k")]
    spans = [(0.0, 0.6, "bench.submit"), (0.6, 1.0, "bench.wait"),
             (2.0, 2.5, "bench.wait"), (2.5, 4.0, "bench.predict")]
    got = devtrace.idle_by_span(ops, spans, (0.0, 6.0))
    assert got == [["bench.predict", 2.0], ["bench.submit", 1.0],
                   ["none", 1.0]]


def test_host_work_labels_a_gap_ahead_of_a_wait():
    ops = [(0.0, 1.0, "k"), (3.0, 4.0, "k")]
    spans = [(1.0, 3.0, "bench.wait"), (1.5, 2.6, "bench.predict")]
    assert devtrace.idle_by_span(ops, spans, (0.0, 4.0)) == \
        [["bench.predict", 2.0]]
    spans = [(1.0, 3.0, "bench.wait"), (1.5, 2.0, "bench.predict")]
    assert devtrace.idle_by_span(ops, spans, (0.0, 4.0)) == \
        [["bench.wait", 2.0]]


def test_summary_averages_busy_over_devices():
    tr = devtrace.Trace(ops={"d0": [(0.0, 1.0, "x")],
                             "d1": [(0.0, 3.0, "x")]},
                        spans=[], window=(0.0, 4.0))
    s = devtrace.summary(tr)
    assert s["busy_s"] == pytest.approx(2.0)
    assert s["window_s"] == pytest.approx(4.0)
    assert s["device_ops"] == [["x", 4.0]]
    assert s["idle_gaps"] == [["none", 4.0]]


@pytest.fixture(scope="module")
def recorded():
    # the CPU client's thread plays the device: the arithmetic is what
    # is checked, not a device number
    return devtrace.read(RECORDED, device_plane="/host:CPU",
                         op_line="tf_XLAPjRtCpuClient")


def test_recorded_trace_spans(recorded):
    names = sorted(n for _, _, n in recorded.spans)
    assert names == ["bench.predict"] * 3 + ["bench.wait"] * 3
    lo, hi = recorded.window
    assert all(lo <= s <= e <= hi for s, e, _ in recorded.spans)
    assert 0.009 < hi - lo < 1.0          # three 3 ms sleeps and more


def test_recorded_trace_arithmetic(recorded):
    ops = [iv for ivs in recorded.ops.values() for iv in ivs]
    w = recorded.window
    busy = devtrace.busy(ops, w)
    gap = sum(e - s for s, e in devtrace.gaps(ops, w))
    assert busy > 0
    assert busy + gap == pytest.approx(w[1] - w[0], rel=1e-12)
    # brute force on a 1 µs grid agrees with the interval union
    lo = w[0]
    grid = np.zeros(int(np.ceil((w[1] - lo) * 1e6)) + 1, bool)
    for s, e, _ in devtrace.clip(ops, w):
        grid[int(round((s - lo) * 1e6)):int(round((e - lo) * 1e6))] = True
    assert busy * 1e6 == pytest.approx(grid.sum(), abs=2 * len(ops) + 2)
    s = devtrace.summary(recorded)
    assert s["busy_s"] == pytest.approx(busy)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(gap)
    labels = {k for k, _ in s["idle_gaps"]}
    assert "bench.wait" in labels          # the sleeps leave the CPU idle
    assert sum(v for _, v in devtrace.op_totals(ops, w, top=10 ** 6)) \
        == pytest.approx(sum(e - s for s, e, _ in devtrace.clip(ops, w)))


def test_read_refuses_a_trace_without_window_or_ops():
    with pytest.raises(ValueError, match="XLA Ops"):
        devtrace.read(RECORDED)           # a CPU trace has no TPU plane
