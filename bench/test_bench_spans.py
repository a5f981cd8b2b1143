"""The program's ``repro.*`` host spans in a trace (``progspans``):
per-name totals, the device-idle time they cover and the idle time no
span covers, by hand and on a small profiler trace recorded on the CPU
(``testdata/cpu_program_spans.xplane.pb``, written by

    PYTHONPATH=src JAX_PLATFORMS=cpu python bench/testdata/record_program_spans.py

: one ``ServingRuntime`` batch before ``bench.window``, three inside it,
each followed by a 3 ms ``bench.wait`` sleep), and the per-call numbers
built on them."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import devtrace  # noqa: E402
import progspans  # noqa: E402

RECORDED = os.path.join(HERE, "testdata", "cpu_program_spans.xplane.pb")
BATCH_SPANS = ["repro.batch", "repro.form", "repro.pad", "repro.quantize",
               "repro.tile_pad", "repro.h2d", "repro.launch", "repro.wait",
               "repro.d2h"]


def test_overlap_of_two_unions_by_hand():
    a = [(0.0, 2.0), (3.0, 5.0), (8.0, 9.0)]
    b = [(1.0, 4.0), (4.5, 8.5)]
    assert progspans.overlap(a, b) == pytest.approx(1.0 + 1.0 + 0.5 + 0.5)
    assert progspans.overlap(a, []) == 0.0
    assert progspans.overlap(b, b) == pytest.approx(3.0 + 4.0)


def test_summary_by_hand():
    ops = {"d0": [(1.0, 2.0, "k"), (6.0, 7.0, "k")]}
    program = [(-3.0, -1.0, "repro.quantize"),     # before the window
               (-0.5, 0.5, "repro.quantize"),      # across its start
               (2.0, 4.0, "repro.batch"),
               (2.0, 3.0, "repro.quantize"),
               (3.0, 3.5, "repro.h2d"),
               (5.0, 6.5, "repro.wait"),
               (9.5, 11.0, "repro.d2h")]           # across its end
    s = progspans.summary(devtrace.Trace(ops, [], (0.0, 10.0)), program)
    assert s["program_s"] == pytest.approx(
        {"repro.quantize": 1.5, "repro.batch": 2.0, "repro.h2d": 0.5,
         "repro.wait": 1.5, "repro.d2h": 0.5})
    # idle: [0,1) [2,6) [7,10) = 8 s; covered by spans [0,0.5) [2,4)
    # [5,6) [9.5,10)
    assert s["idle_s"] == pytest.approx(8.0)
    assert s["program_idle_s"] == pytest.approx(
        {"repro.quantize": 1.5, "repro.batch": 2.0, "repro.h2d": 0.5,
         "repro.wait": 1.0, "repro.d2h": 0.5})
    assert s["idle_unspanned_s"] == pytest.approx(8.0 - 0.5 - 2.0 - 1.0
                                                  - 0.5)


def test_idle_is_summed_over_devices():
    ops = {"d0": [(1.0, 2.0, "k")], "d1": [(0.0, 3.0, "k")]}
    tr = devtrace.Trace(ops, [], (0.0, 4.0))
    bare = progspans.summary(tr, [])
    assert bare["program_s"] == {}
    assert bare["idle_s"] == pytest.approx(4.0)     # 3 s on d0, 1 s on d1
    assert bare["idle_unspanned_s"] == pytest.approx(4.0)
    s = progspans.summary(tr, [(0.0, 4.0, "repro.batch")])
    assert s["idle_unspanned_s"] == pytest.approx(0.0)
    assert s["program_idle_s"] == pytest.approx({"repro.batch": 4.0})


@pytest.fixture(scope="module")
def recorded():
    # the CPU client's threads play the device: the arithmetic is what
    # is checked, not a device number
    tr = devtrace.read(RECORDED, device_plane="/host:CPU",
                       op_line="tf_XLAPjRtCpuClient")
    return tr, progspans.read(RECORDED)


def test_recorded_spans_outside_the_window_are_ignored(recorded):
    tr, program = recorded
    lo, hi = tr.window
    assert sorted({n for *_, n in program}) == sorted(BATCH_SPANS)
    before = [iv for iv in program if iv[1] <= lo]
    assert sorted(n for *_, n in before) == sorted(BATCH_SPANS)
    s = progspans.summary(tr, program)
    assert sorted(s["program_s"]) == sorted(BATCH_SPANS)
    for name in BATCH_SPANS:
        inside = [(a, b) for a, b, n in program
                  if n == name and lo <= a and b <= hi]
        assert len(inside) == 3
        assert s["program_s"][name] == pytest.approx(
            sum(b - a for a, b in inside), rel=1e-12)
    # every span of a batch lies inside its repro.batch
    batches = [(a, b) for a, b, n in program if n == "repro.batch"]
    for a, b, n in program:
        assert any(a0 <= a and b <= b0 for a0, b0 in batches), n


def _grid(tr, program):
    """Brute force on a 1 µs grid: the device-idle cells of the window
    and, per span name, the cells its spans cover."""
    lo, hi = tr.window
    n = int(np.ceil((hi - lo) * 1e6)) + 1

    def cells(ivs):
        g = np.zeros(n, bool)
        for s, e in ivs:
            g[int(round((max(s, lo) - lo) * 1e6)):
              int(round((min(e, hi) - lo) * 1e6))] = True
        return g

    ops = [(s, e) for ivs in tr.ops.values() for s, e, _ in ivs
           if e > lo and s < hi]
    idle = ~cells(ops)
    idle[int(round((hi - lo) * 1e6)):] = False
    by = {name: cells([(s, e) for s, e, m in program
                       if m == name and e > lo and s < hi])
          for name in BATCH_SPANS}
    return idle, by


def test_recorded_idle_attribution_by_hand(recorded):
    tr, program = recorded
    s = progspans.summary(tr, program)
    d = devtrace.summary(tr)
    idle, by = _grid(tr, program)
    tol = 2e-6 * (len(program) + sum(map(len, tr.ops.values())) + 2)
    assert s["idle_s"] == pytest.approx(idle.sum() * 1e-6, abs=tol)
    assert s["idle_s"] == pytest.approx(
        (d["window_s"] - d["busy_s"]) * len(tr.ops))
    for name in BATCH_SPANS:
        assert s["program_idle_s"][name] == pytest.approx(
            (idle & by[name]).sum() * 1e-6, abs=tol), name
    anyspan = np.logical_or.reduce(list(by.values()))
    assert s["idle_unspanned_s"] == pytest.approx(
        (idle & ~anyspan).sum() * 1e-6, abs=tol)
    # the three 3 ms sleeps are idle that no repro.* span covers
    assert s["idle_unspanned_s"] > 0.009
    # a batch covers its children: the union is repro.batch's own cover
    assert s["idle_s"] - s["idle_unspanned_s"] == pytest.approx(
        s["program_idle_s"]["repro.batch"], abs=tol)


def test_per_call_numbers_on_the_recorded_trace(recorded):
    tr, program = recorded
    s = progspans.summary(tr, program)
    for name in ("quantize", "h2d", "d2h"):
        assert progspans.span_ms(s, name, 3) == pytest.approx(
            s["program_s"]["repro." + name] / 3 * 1e3)
    got = progspans.idle_unspanned(s)
    assert got == pytest.approx(100.0 * s["idle_unspanned_s"] / s["idle_s"])
    assert 0 < got < 100


def test_per_call_numbers_without_program_spans(recorded):
    """A program that writes no ``repro.*`` span (an older commit) gives
    no number, and no error."""
    tr, _ = recorded
    s = progspans.summary(tr, [])
    assert progspans.span_ms(s, "quantize", 3) is None
    assert progspans.idle_unspanned(s) is None
    assert progspans.span_ms(progspans.summary(tr, recorded[1]),
                             "quantize", 0) is None
