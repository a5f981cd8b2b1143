"""End-to-end observability: metrics, tracing, retrace detection,
structured logging, and Prometheus/JSON exposition.

The paper's finding — the best implementation depends on the forest AND
the device — turns a deployment into a stream of runtime decisions
(engine choice, SLO batching knobs, cascade exits, compile events).
This package makes that stream observable (docs/OBSERVABILITY.md):

  * ``obs.metrics``  — thread-safe registry: counters, gauges, bounded
    histograms (``Reservoir``-backed), per-tenant labels, process-wide
    default instance, near-zero cost when disabled;
  * ``obs.trace``    — per-request spans (queue/form/pad/compute/sync
    phases, and the predictor's sub-phases) in a bounded ring buffer,
    retrievable as JSON; ``phase``, the one helper that writes a
    ``repro.*`` profiler span and feeds those phases;
  * ``obs.retrace``  — jit trace-cache watchers: post-warmup compiles
    surface as anomalies instead of silent latency spikes;
  * ``obs.log``      — structured ``key=value`` logger for the launch
    drivers (quiet-by-default under pytest);
  * ``obs.expo``     — Prometheus text + JSON snapshot served from a
    stdlib HTTP thread (``ServingRuntime.serve_metrics``);
  * ``obs.serving``  — the serving metric catalog (the contract
    ``check_engines.py --obs`` asserts against a live scrape).

Import discipline: nothing here imports the rest of ``repro`` at module
scope (``Reservoir`` is pulled lazily), so the serving runtime, the
autotuner, and the launch drivers can all import ``repro.obs`` freely
without cycles.
"""
from .expo import MetricsServer, json_snapshot
from .log import StructLogger, get_logger, set_level
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, set_default_registry)
from .retrace import CompileWatch, fn_cache_size, jit_cache_size
from .serving import METRIC_CATALOG, ServingMetrics
from .trace import (PHASES, PREDICTOR_PHASES, Span, TraceBuffer, collect,
                    phase)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "set_default_registry",
    "Span", "TraceBuffer", "PHASES", "PREDICTOR_PHASES", "phase",
    "collect",
    "CompileWatch", "fn_cache_size", "jit_cache_size",
    "StructLogger", "get_logger", "set_level",
    "MetricsServer", "json_snapshot",
    "METRIC_CATALOG", "ServingMetrics",
]
