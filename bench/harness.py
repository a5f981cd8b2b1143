"""What a traffic driver gets from the harness and hands back to it."""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import devtrace
from cellmodel import Model

# JAX events that mean a program was traced, compiled or loaded
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


@dataclass
class Ctx:
    """One run of one cell."""
    name: str
    model: Model
    traffic: dict
    seed: int
    seconds: float
    tracing: bool
    cache_dir: str
    device_kind: str
    compiles: int = 0               # COMPILE_EVENTS seen so far
    trace: Optional[dict] = None    # devtrace.summary of the window
    setup: Optional["Setup"] = None

    @property
    def engine_cache(self) -> str:
        return os.path.join(self.cache_dir, "engines.json")

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.cache_dir, "trace")

    def choose_kw(self) -> dict:
        """Engine selection as the configuration states it: the system's
        own sweep, over the candidates the configuration pins, if any."""
        kw = {"cache_path": self.engine_cache}
        if self.model.cfg.get("engines"):
            kw["engines"] = tuple(self.model.cfg["engines"])
        return kw

    def on_event(self, event: str, _secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def span(self, name: str):
        """A host span ``bench.<name>`` in the profiler's trace, when
        tracing; nothing otherwise."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        """The measured window: the profiler records it when tracing,
        and it yields a one-entry dict whose ``compiles`` holds, after
        the window, how many programs were traced or compiled in it."""
        import jax
        out = {}
        gc.collect()
        gc.freeze()
        if self.tracing:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        before = self.compiles
        try:
            with self.span("window"):
                yield out
        finally:
            out["compiles"] = self.compiles - before
            if self.tracing:
                jax.profiler.stop_trace()
            gc.unfreeze()
        if self.tracing:
            self.trace = devtrace.summary(devtrace.read(
                devtrace.latest(self.trace_dir)))
            shutil.rmtree(self.trace_dir, ignore_errors=True)


@dataclass
class Outcome:
    """What one measured window produced."""
    e2e: dict                         # end-to-end metric name → value
    attempted: int
    failed: int
    steps: list                       # [(real rows, wall seconds)] per step
    got: np.ndarray                   # served scores of the checked rows
    rows: np.ndarray                  # distinct checked rows
    row_ids: np.ndarray               # for each row of got, its row in rows
    missing: int = 0                  # checked answers that never came
    queue_ms: Optional[np.ndarray] = None
    latency_ms: Optional[np.ndarray] = None   # per request, arrival order
    counters: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)


@dataclass
class Setup:
    """What set-up cost, apart from the process's own start."""
    engine: str
    autotune_s: float
    warmup_s: float


def wait_until(t: float) -> None:
    """Sleep until the host clock reads ``t`` (perf_counter seconds)."""
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(min(dt, 5e-4))
