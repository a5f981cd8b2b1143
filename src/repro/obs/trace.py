"""Request tracing: where did this request's 12 ms go?

A ``Span`` is one served request's phase breakdown, stamped by the
serving layer as the batch it rode in moves through dispatch
(``inference.runtime.ServingRuntime._run_batch``):

  * ``queue_ms``   — submit → the dispatch rule fired (arrival-relative,
    measured on the runtime's clock, so virtual-clock tests stamp
    deterministic values);
  * ``form_ms``    — stacking the drained requests into one (B, d) batch;
  * ``pad_ms``     — zero-padding to the power-of-two bucket (plain
    engines only; cascade/Pallas tenants bucket internally);
  * ``compute_ms`` — the predictor call until it returns.  Every
    registry predictor ends in host scores, so this is the whole call:
    quantization, copy-in, kernel and copy-out;
  * ``sync_ms``    — ``jax.block_until_ready`` on what the call
    returned: about 0 for a predictor that already returned host scores.

Inside ``compute_ms`` the predictor's own phases (``PREDICTOR_PHASES``:
``quantize_ms``, ``tile_pad_ms``, ``h2d_ms``, ``launch_ms``,
``wait_ms``, ``d2h_ms``) come from ``phase`` spans in its host path
(``core.registry.BasePredictor._score``).

``phase(name, **args)`` is the one span helper: a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (visible in a
profiler trace, on the device ops' clock) which, while a per-thread
``collect()`` is active, also adds its elapsed ms to the collector
under ``<name>_ms``.  The serving layer opens a collector per batch
only when observability is on; with no collector and no profiler a
phase costs one ``TraceMe`` enter and exit.

Sub-phase durations come from ``time.perf_counter`` deltas (monotonic —
the same contract as the serving stats); only ``queue_ms`` uses the
injectable runtime clock, which keeps spans meaningful under both the
threaded loop and the virtual-clock ``pump``/``flush`` twin.

``TraceBuffer`` is a bounded, thread-safe ring of recent spans — the
flight recorder an operator pulls as JSON from the metrics endpoint
(``GET /traces``) after a latency spike, without grepping logs or
re-running traffic.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation

#: the predictor's host-path phases, in call order; all inside compute_ms
PREDICTOR_PHASES = ("quantize_ms", "tile_pad_ms", "h2d_ms", "launch_ms",
                    "wait_ms", "d2h_ms")
#: canonical phase order (docs/OBSERVABILITY.md)
PHASES = ("queue_ms", "form_ms", "pad_ms", "compute_ms", "sync_ms") \
    + PREDICTOR_PHASES

_local = threading.local()


class _Timed:
    """A span that also adds its elapsed ms to a collector."""
    __slots__ = ("_span", "_acc", "_key", "_t0")

    def __init__(self, span: TraceAnnotation, acc: dict, key: str):
        self._span, self._acc, self._key = span, acc, key

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = (time.perf_counter() - self._t0) * 1e3
        self._acc[self._key] = self._acc.get(self._key, 0.0) + dt
        return self._span.__exit__(*exc)


def phase(name: str, **args):
    """Context manager: the profiler span ``repro.<name>`` with ``args``
    as its metadata; inside ``collect()`` on this thread it also adds
    its elapsed ms to the collector under ``<name>_ms``."""
    span = TraceAnnotation(f"repro.{name}", **args)
    acc = getattr(_local, "acc", None)
    return span if acc is None else _Timed(span, acc, f"{name}_ms")


@contextlib.contextmanager
def collect():
    """Collect the ms of every ``phase`` this thread enters, summed per
    name, into the yielded dict (the enclosing collector, if any, is
    restored on exit)."""
    prev = getattr(_local, "acc", None)
    _local.acc = acc = {}
    try:
        yield acc
    finally:
        _local.acc = prev


@dataclass
class Span:
    """One request's trace through the serving runtime."""
    rid: int
    tenant: str
    arrival_s: float
    batch_size: int = 0               # requests in the batch it rode in
    bucket: int = 0                   # padded batch the engine saw
    phases: dict = field(default_factory=dict)      # phase -> ms
    total_ms: Optional[float] = None  # submit -> scores on the host
    ok: bool = True
    error: Optional[str] = None

    def to_dict(self) -> dict:
        """JSON-clean dict (what /traces serves)."""
        out = {
            "rid": self.rid,
            "tenant": self.tenant,
            "arrival_s": float(self.arrival_s),
            "batch_size": int(self.batch_size),
            "bucket": int(self.bucket),
            "phases": {k: float(v) for k, v in self.phases.items()},
            "total_ms": (float(self.total_ms)
                         if self.total_ms is not None else None),
            "ok": bool(self.ok),
        }
        if self.error is not None:
            out["error"] = self.error
        return out


class TraceBuffer:
    """Bounded ring of recent spans (newest last), thread-safe."""

    def __init__(self, cap: int = 256):
        if cap < 1:
            raise ValueError(f"trace buffer cap must be >= 1, got {cap}")
        self.cap = cap
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=cap)
        self.n_added = 0              # spans ever recorded (exact)

    def add(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)
            self.n_added += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def recent(self, n: Optional[int] = None) -> list:
        """The most recent ``n`` spans (all retained by default) as
        JSON-clean dicts, oldest first."""
        with self._lock:
            spans = list(self._ring)
        if n is not None:
            spans = spans[-int(n):]
        return [s.to_dict() for s in spans]

    def to_json(self, n: Optional[int] = None) -> str:
        return json.dumps(self.recent(n), indent=1)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
