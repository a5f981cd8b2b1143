"""The program's own host spans in a profiler trace: ``repro.*`` events,
written by ``repro.obs.trace.phase`` on the same clock as the device's
operations.

Kept apart from ``devtrace``, whose ``Trace`` holds the benchmark's
``bench.*`` spans: ``read`` pulls the ``repro.*`` events out of a
``.xplane.pb`` file, and ``summary`` reduces them against a
``devtrace.Trace`` of the same file:

  * ``program_s`` — seconds per span name inside the window (spans
    clipped to it, those outside it ignored);
  * ``program_idle_s`` — device-idle seconds each name covers;
  * ``idle_s`` and ``idle_unspanned_s`` — device-idle seconds, and those
    no ``repro.*`` span covers (summed over devices).

``span_ms`` and ``idle_unspanned`` turn a summary into the per-call
numbers a reader would report.  No benchmark run calls this module yet:
``harness.Ctx.window`` keeps only ``devtrace.summary`` of a trace.
"""
from __future__ import annotations

from collections import defaultdict

import devtrace

PREFIX = "repro."


def read(path: str) -> list:
    """``[(start, end, name)]`` in seconds of every ``repro.*`` event on
    the host planes of the trace at ``path``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                           for e in line.events
                           if e.name.startswith(PREFIX))
    return out


def overlap(a, b) -> float:
    """Seconds common to two lists of sorted, disjoint ``(start, end)``
    pairs (``devtrace.union`` or ``devtrace.gaps`` output)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def summary(tr: devtrace.Trace, program: list) -> dict:
    """``program``'s spans against the device ops and window of ``tr``
    (keys in the module docstring)."""
    w = tr.window
    inside = devtrace.clip(program, w)
    by: dict = defaultdict(list)
    for s, e, name in inside:
        by[name].append((s, e))
    every = devtrace.union(inside)
    idle_s = unspanned = 0.0
    covered: dict = defaultdict(float)
    for ivs in tr.ops.values():
        g = devtrace.gaps(ivs, w)
        idle = sum(e - s for s, e in g)
        idle_s += idle
        unspanned += idle - overlap(g, every)
        for name, spans in by.items():
            covered[name] += overlap(g, devtrace.union(spans))
    return {
        "program_s": {k: sum(e - s for s, e in v) for k, v in by.items()},
        "program_idle_s": dict(covered),
        "idle_s": idle_s,
        "idle_unspanned_s": unspanned,
    }


def span_ms(summ: dict, name: str, calls: int) -> float | None:
    """Mean ms per call of the ``repro.<name>`` spans; ``None`` where the
    program wrote none."""
    s = summ["program_s"].get(PREFIX + name)
    return None if s is None or not calls else s / calls * 1e3


def idle_unspanned(summ: dict) -> float | None:
    """Share, %, of the device-idle time that no ``repro.*`` span covers;
    ``None`` where the program wrote none."""
    if not summ["program_s"]:
        return None
    idle = summ["idle_s"]
    return 100.0 * summ["idle_unspanned_s"] / idle if idle else 0.0
