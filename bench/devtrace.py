"""Reduce a profiler trace to device busy time, idle gaps and op totals.

The reduction works on plain interval lists, so it is the same for any
trace and can be checked by hand:

  * ``busy`` — the union of the device-op intervals inside the window;
  * ``gaps`` — the window minus that union, each gap labelled by the
    benchmark's own host span (``bench.*``) that covers it;
  * ``op_totals`` — seconds per device-op name.

``read`` pulls those intervals out of a ``.xplane.pb`` file: device ops
from each device plane's op line, host spans from the host plane.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
# spans of host work, ahead of spans in which the host only waits: the
# serving worker's predictor calls overlap the client's waits
BUSY_SPANS = ("bench.predict", "bench.submit")


@dataclass
class Trace:
    """Intervals in seconds on the trace's clock."""
    ops: dict            # device plane name → [(start, end, op name)]
    spans: list          # [(start, end, span name)] on every host thread
    window: tuple        # (start, end) of the measured window


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` pairs."""
    out: list = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def busy(intervals, window) -> float:
    """Seconds of ``window`` in which at least one interval is open."""
    return sum(e - s for s, e in union(clip(intervals, window)))


def gaps(intervals, window) -> list:
    """``(start, end)`` stretches of ``window`` that no interval covers."""
    lo, hi = window
    out, t = [], lo
    for s, e in union(clip(intervals, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Spans:
    """Host spans indexed by name, for labelling many gaps quickly."""

    def __init__(self, spans):
        by: dict = defaultdict(list)
        for s, e, name in spans:
            by[name].append((s, e))
        self._by = {}
        for name, ivs in by.items():
            a = np.array(sorted(ivs), dtype=np.float64).reshape(-1, 2)
            self._by[name] = (a[:, 0], a[:, 1],
                              float((a[:, 1] - a[:, 0]).max()))

    def cover(self, gap) -> dict:
        """Seconds of ``gap`` each span name covers (overlaps within one
        name counted once per span)."""
        g0, g1 = gap
        out = {}
        for name, (st, en, longest) in self._by.items():
            i = bisect.bisect_left(st, g0 - longest)
            j = bisect.bisect_left(st, g1)
            if i < j:
                c = (np.minimum(en[i:j], g1)
                     - np.maximum(st[i:j], g0)).clip(0).sum()
                if c > 0:
                    out[name] = float(c)
        return out

    def label(self, gap) -> str:
        """What the host did in ``gap``: the first of ``BUSY_SPANS`` that
        covers half of it, else the span name covering most of it,
        ``"none"`` if no span does."""
        cover = self.cover(gap)
        for name in BUSY_SPANS:
            if cover.get(name, 0.0) >= 0.5 * (gap[1] - gap[0]):
                return name
        return max(cover, key=cover.get) if cover else "none"


def idle_by_span(intervals, spans, window, top: int = 10) -> list:
    """``[[label, seconds]]``: idle device time per host span name,
    largest first."""
    index = spans if isinstance(spans, Spans) else Spans(spans)
    total: dict = defaultdict(float)
    for g in gaps(intervals, window):
        total[index.label(g)] += g[1] - g[0]
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            ][:top]


def op_totals(intervals, window, top: int = 10) -> list:
    """``[[op name, seconds]]`` inside ``window``, largest first."""
    total: dict = defaultdict(float)
    for s, e, name in clip(intervals, window):
        total[name] += e - s
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            ][:top]


def latest(directory: str) -> str:
    """The newest ``.xplane.pb`` file under ``directory``."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def read(path: str, device_plane: str = DEVICE_PLANE,
         op_line: str = OP_LINE) -> Trace:
    """Device ops of every plane whose name starts with ``device_plane``
    (from its lines whose names start with ``op_line``), and every
    ``bench.*`` span on the host planes."""
    from jax.profiler import ProfileData
    ops: dict = {}
    spans: list = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(device_plane)
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if device and line.name.startswith(op_line):
                ops.setdefault(plane.name, []).extend(
                    (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    for e in line.events)
            elif host:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                    if e.name == WINDOW_SPAN:
                        window = iv
                    else:
                        spans.append((*iv, e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    if not ops:
        raise ValueError(f"{path}: no {op_line!r} events on any "
                         f"{device_plane!r} plane")
    return Trace(ops, spans, window)


def summary(tr: Trace) -> dict:
    """``busy_s`` averaged over the devices, ``window_s``, and the
    ``breakdown`` lists, op totals and idle gaps summed over devices."""
    w = tr.window
    all_ops = [iv for ivs in tr.ops.values() for iv in ivs]
    idle: dict = defaultdict(float)
    index = Spans(tr.spans)
    for ivs in tr.ops.values():
        for k, v in idle_by_span(ivs, index, w, top=1 << 30):
            idle[k] += v
    return {
        "busy_s": sum(busy(ivs, w) for ivs in tr.ops.values()) / len(tr.ops),
        "window_s": w[1] - w[0],
        "device_ops": op_totals(all_ops, w),
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(),
                                                key=lambda kv: -kv[1])][:10],
    }
