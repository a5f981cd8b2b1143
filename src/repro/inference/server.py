"""Batched inference serving for both engines of the framework.

The paper's deployment target is continuous streams of measurements on IoT
devices; the framework generalises that to a server abstraction:

  * ``MicroBatcher`` — groups incoming requests into engine-shaped batches
    under a max-latency budget (classic dynamic batching: dispatch when
    ``max_batch`` is reached OR the oldest request exceeds ``max_wait_ms``).
  * ``ForestServer`` — tree-ensemble scoring behind a micro-batcher, any
    core engine (bitvector / rapidscorer / gemm / native / pallas).
  * ``LMServer`` — prefill + KV-cache decode for the LM model zoo
    (CPU-reduced configs in tests; the same class drives the production
    mesh on real hardware).

Requests are processed in arrival order; the batcher is deterministic given
arrival timestamps, so tests can assert exact batching decisions.

Timestamps: all *default* clocks here are ``time.perf_counter()`` —
monotonic, so a latency can never go negative because NTP stepped the
wall clock mid-request.  Callers that pass explicit ``arrival_s`` /
``now_s`` values (virtual clocks — the deterministic-test contract, and
``repro.launch.serve``'s replayed arrival traces) are untouched: the
server only ever *subtracts* timestamps, so any consistent timebase
works.

The concurrent multi-tenant front door (threaded request loop, adaptive
batching, shape warmup) lives in ``repro.inference.runtime`` and is
built out of these parts — see docs/SERVING.md.
"""
from __future__ import annotations

import contextlib
import random
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import collect, phase


# --------------------------------------------------------------------------- #
# Requests / stats
# --------------------------------------------------------------------------- #
@dataclass
class Request:
    rid: int
    payload: Any                      # (d,) features | (S,) prompt tokens
    arrival_s: float
    done_s: Optional[float] = None
    result: Any = None

    @property
    def latency_ms(self) -> Optional[float]:
        if self.done_s is None:
            return None
        return (self.done_s - self.arrival_s) * 1e3


class Reservoir:
    """Bounded sample of a value stream: exact below ``cap``, a uniform
    random sample above (Vitter's Algorithm R, deterministic seed), with
    the running count and sum kept exactly so ``mean()`` is always exact
    while percentiles come from the retained sample.

    This replaces the unbounded ``ServerStats`` lists: a server under
    sustained traffic holds O(cap) floats no matter how many requests it
    has completed, and ``summary()`` percentiles stay O(cap) work.
    Below the cap the sample IS the full stream, so short runs (every
    test, every benchmark window) lose nothing.
    """

    __slots__ = ("cap", "n", "total", "_sample", "_rng")

    def __init__(self, cap: int = 4096, seed: int = 0):
        if cap < 1:
            raise ValueError(f"reservoir cap must be >= 1, got {cap}")
        self.cap = cap
        self.n = 0                       # values ever observed (exact)
        self.total = 0.0                 # running sum (exact mean)
        self._sample: list[float] = []
        self._rng = random.Random(seed)

    def append(self, v: float) -> None:
        v = float(v)
        self.n += 1
        self.total += v
        if len(self._sample) < self.cap:
            self._sample.append(v)
        else:
            # Algorithm R: keep each of the n values with prob cap/n
            j = self._rng.randrange(self.n)
            if j < self.cap:
                self._sample[j] = v

    def extend(self, it) -> None:
        for v in it:
            self.append(v)

    def mean(self) -> float:
        return self.total / self.n if self.n else 0.0

    def percentile(self, q) -> float:
        if not self._sample:
            raise ValueError("percentile of an empty reservoir")
        return float(np.percentile(self._sample, q))

    # list-compatible surface: existing callers iterate, truth-test,
    # np.asarray, and compare against plain lists
    def __len__(self) -> int:
        return len(self._sample)

    def __iter__(self):
        return iter(self._sample)

    def __bool__(self) -> bool:
        return self.n > 0

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._sample, dtype=dtype)

    def __eq__(self, other):
        if isinstance(other, Reservoir):
            return self._sample == other._sample and self.n == other.n
        if isinstance(other, (list, tuple)):
            return self._sample == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return (f"Reservoir(n={self.n}, cap={self.cap}, "
                f"retained={len(self._sample)})")


@dataclass
class ServerStats:
    n_requests: int = 0
    n_batches: int = 0
    batch_sizes: Reservoir = field(default_factory=Reservoir)
    latencies_ms: Reservoir = field(default_factory=Reservoir)
    # per-batch phase breakdown: the whole predictor call (host scores
    # for every registry predictor) vs the wait on what it returned —
    # docs/OBSERVABILITY.md
    compute_ms: Reservoir = field(default_factory=Reservoir)
    sync_ms: Reservoir = field(default_factory=Reservoir)
    # cascade serving: cumulative per-stage exit counts (empty unless the
    # predictor reports them — see ForestServer._run / docs/CASCADE.md)
    stage_exit_counts: list = field(default_factory=list)

    def record_batch(self, reqs: list[Request]) -> None:
        if not reqs:                   # zero-request batch: stats unchanged
            return
        self.n_batches += 1
        self.n_requests += len(reqs)
        self.batch_sizes.append(len(reqs))
        self.latencies_ms.extend(
            r.latency_ms for r in reqs if r.latency_ms is not None)

    def record_phases(self, compute_ms: float, sync_ms: float) -> None:
        """Record one batch's predictor-call / wait split."""
        self.compute_ms.append(compute_ms)
        self.sync_ms.append(sync_ms)

    def record_exits(self, counts) -> None:
        """Accumulate a cascade predictor's per-stage exit counts for the
        batch just served (``counts`` is its ``last_exit_counts``)."""
        if counts is None:
            return
        counts = [int(c) for c in counts]
        if len(self.stage_exit_counts) < len(counts):
            self.stage_exit_counts.extend(
                [0] * (len(counts) - len(self.stage_exit_counts)))
        for i, c in enumerate(counts):
            self.stage_exit_counts[i] += c

    def summary(self) -> dict:
        # no completed request → no latency distribution: report null,
        # not the 0.0 percentiles of a zeros(1) placeholder (a dashboard
        # reading p99=0.0 would conclude the server is infinitely fast)
        lat = self.latencies_ms if self.latencies_ms else None
        out = {
            "n_requests": self.n_requests,
            "n_batches": self.n_batches,
            "mean_batch": self.batch_sizes.mean(),
            "p50_ms": lat.percentile(50) if lat is not None else None,
            "p99_ms": lat.percentile(99) if lat is not None else None,
        }
        if self.compute_ms:
            out["compute_p50_ms"] = self.compute_ms.percentile(50)
            out["sync_p50_ms"] = self.sync_ms.percentile(50) \
                if self.sync_ms else None
        if self.stage_exit_counts:
            tot = sum(self.stage_exit_counts)
            out["exit_fractions"] = [c / max(tot, 1)
                                     for c in self.stage_exit_counts]
        return out


# --------------------------------------------------------------------------- #
# Micro-batcher
# --------------------------------------------------------------------------- #
class MicroBatcher:
    """Dispatch rule: flush when ``len(queue) >= max_batch`` or when
    ``now - oldest.arrival_s >= max_wait_ms``. Pure decision logic —
    unit-testable without a clock."""

    def __init__(self, max_batch: int = 64, max_wait_ms: float = 5.0):
        if max_batch < 1:
            # drain() would emit empty batches forever (flush() spins)
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.queue: list[Request] = []

    def add(self, req: Request) -> None:
        self.queue.append(req)

    def ready(self, now_s: float) -> bool:
        if not self.queue:
            return False
        if len(self.queue) >= self.max_batch:
            return True
        return (now_s - self.queue[0].arrival_s) * 1e3 >= self.max_wait_ms

    def drain(self) -> list[Request]:
        batch, self.queue = (self.queue[:self.max_batch],
                             self.queue[self.max_batch:])
        return batch


# --------------------------------------------------------------------------- #
# Forest serving
# --------------------------------------------------------------------------- #
class ForestServer:
    def __init__(self, predictor, max_batch: int = 256,
                 max_wait_ms: float = 2.0, *, obs=None,
                 obs_label: str = "forest"):
        self.predictor = predictor
        self.batcher = MicroBatcher(max_batch, max_wait_ms)
        self.stats = ServerStats()
        self.engine_choice = None          # set by from_forest()
        self._rid = 0
        # optional catalog instrumentation (docs/OBSERVABILITY.md):
        # obs=True → the process default registry; a MetricsRegistry /
        # ServingMetrics instance → that.  The synchronous server stays
        # uninstrumented by default — ServingRuntime is the production
        # front door and defaults the other way.
        self.obs_label = obs_label
        if obs is None or obs is False:
            self._obs = None
        else:
            from ..obs.metrics import MetricsRegistry, get_registry
            from ..obs.serving import ServingMetrics
            if obs is True:
                obs = ServingMetrics(get_registry())
            elif isinstance(obs, MetricsRegistry):
                obs = ServingMetrics(obs)
            self._obs = obs

    _CACHE_UNSET = object()       # distinguish "not given" from None

    @classmethod
    def from_forest(cls, forest, *, max_batch: int = 256,
                    max_wait_ms: float = 2.0, engines=None,
                    n_devices: int = 1,
                    cache_path=_CACHE_UNSET, **choose_kw) -> "ForestServer":
        """Build a server on the autotuned fastest engine for this forest.

        The dispatch batch cap is the autotune batch: the winner is picked
        for the batch shape the micro-batcher will actually emit.  The
        decision comes from ``core.engine_select``'s cache when one exists
        (in-memory or the JSON file), so restarts skip the sweep.
        ``n_devices > 1`` serves the winner tree-sharded across the device
        mesh (``core.shard``); the autotune cache key includes the device
        count, so single- and multi-device decisions never alias.
        ``cascade_specs=`` (forwarded to ``choose``) adds confidence-gated
        staged candidates — a cascade winner serves through the same
        micro-batcher, with per-stage exit fractions reported in
        ``ServerStats.summary()``; ``opt_levels=`` (also forwarded) adds
        optimizer middle-end variants (``qs@O2``, docs/OPTIM.md) whose
        serving interface is unchanged (full-width rows).  ``cache_path=None`` disables the disk
        layer (as in ``choose``); omitting it uses the default cache
        file."""
        from ..core import engine_select
        kw = dict(choose_kw)
        if cache_path is not cls._CACHE_UNSET:
            kw["cache_path"] = cache_path
        choice = engine_select.choose(forest, max_batch, engines=engines,
                                      n_devices=n_devices, **kw)
        srv = cls(choice.predictor, max_batch=max_batch,
                  max_wait_ms=max_wait_ms)
        srv.engine_choice = choice
        return srv

    def save(self, path) -> None:
        """Persist the compiled serving artifact (docs/FORMATS.md): the
        engine's device arrays + the serving config, so a cold restart
        skips both the autotune sweep and recompilation.  The predictor
        must come from a serializable engine (``EngineSpec.serial_arrays``
        — tree-sharded and Pallas predictors are not; keep the forest and
        rebuild those).  Cascade predictors persist as kind=cascade
        artifacts: every stage's arrays plus the gate thresholds."""
        from .. import io
        # engine_choice is an EngineChoice after from_forest() but a bare
        # name string after load() — persist the name through both, so a
        # load → save cycle keeps it
        extra = {"server": {"max_batch": self.batcher.max_batch,
                            "max_wait_ms": self.batcher.max_wait_ms,
                            "engine_choice": getattr(self.engine_choice,
                                                     "engine",
                                                     self.engine_choice)}}
        io.save_predictor(self.predictor, path, extra=extra)

    @classmethod
    def load(cls, path) -> "ForestServer":
        """Cold-start a server from a ``save()`` artifact: predictions are
        bit-identical to the saved predictor's, no sweep, no recompile.
        ``engine_choice`` on the restored server is the winning engine's
        *name* (the timings/predictor of the original ``EngineChoice``
        were not persisted)."""
        from .. import io
        pred, header = io.load_predictor(path, return_header=True)
        scfg = header.get("server") or {}
        srv = cls(pred, max_batch=int(scfg.get("max_batch", 256)),
                  max_wait_ms=float(scfg.get("max_wait_ms", 2.0)))
        srv.engine_choice = scfg.get("engine_choice")
        return srv

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        """Normalized class scores (paper §4) from the serving engine —
        synchronous path, bypasses the micro-batcher."""
        return self.predictor.predict_proba(X)

    def submit(self, features: np.ndarray,
               arrival_s: Optional[float] = None) -> Request:
        # default timestamps are monotonic (perf_counter): latency is a
        # timestamp difference, and the wall clock can step backwards
        # (NTP) mid-request — virtual-clock callers pass arrival_s
        self._rid += 1
        req = Request(self._rid, np.asarray(features),
                      arrival_s if arrival_s is not None
                      else time.perf_counter())
        self.batcher.add(req)
        return req

    def poll(self, now_s: Optional[float] = None) -> list[Request]:
        """Flush if the dispatch rule fires; returns completed requests."""
        now = now_s if now_s is not None else time.perf_counter()
        if not self.batcher.ready(now):
            return []
        return self._run(self.batcher.drain(), now)

    def flush(self, now_s: Optional[float] = None) -> list[Request]:
        """Unconditional drain (shutdown path)."""
        done = []
        now = now_s if now_s is not None else time.perf_counter()
        while self.batcher.queue:
            done.extend(self._run(self.batcher.drain(), now))
        return done

    def _run(self, reqs: list[Request], now_s: float) -> list[Request]:
        if not reqs:                   # empty flush/drain: no-op, no stats
            return []
        o = self._obs if (self._obs is not None and self._obs.enabled) \
            else None
        n = len(reqs)
        # the predictor's own phase spans land in ``sub`` when obs is on
        with phase("batch", tenant=self.obs_label, n=n, bucket=n), \
                (collect() if o is not None
                 else contextlib.nullcontext()) as sub:
            with phase("form"):
                X = np.stack([r.payload for r in reqs])
            t0 = time.perf_counter()
            scores = self.predictor.predict(X)
            t_compute = time.perf_counter()
            # a predictor returning device arrays has only *launched* the
            # work when predict returns — block before stamping done_s or
            # the recorded latency understates reality
            jax.block_until_ready(scores)
            t_sync = time.perf_counter()
        # completion on the caller's clock: virtual arrival time + real
        # compute time (keeps latency stats consistent under virtual clocks)
        done_s = (now_s if now_s is not None else t0) + (t_sync - t0)
        for r, s in zip(reqs, scores):
            r.result = s
            r.done_s = done_s
        compute_ms = (t_compute - t0) * 1e3
        sync_ms = (t_sync - t_compute) * 1e3
        self.stats.record_batch(reqs)
        self.stats.record_phases(compute_ms, sync_ms)
        # cascade predictors report which stage each row exited at; the
        # stats aggregate them so ServerStats.summary() can show the
        # per-stage exit fractions of the served traffic
        exits = getattr(self.predictor, "last_exit_counts", None)
        self.stats.record_exits(exits)
        if o is not None:
            tid = self.obs_label
            o.batches_total.labels(tenant=tid).inc()
            o.batch_size.labels(tenant=tid).observe(float(n))
            for p, v in dict(sub, compute_ms=compute_ms,
                             sync_ms=sync_ms).items():
                o.phase_ms.labels(tenant=tid, phase=p).observe(v)
            req_ctr = o.requests_total.labels(tenant=tid)
            lat_hist = o.latency_ms.labels(tenant=tid)
            queue_hist = o.phase_ms.labels(tenant=tid, phase="queue_ms")
            for r in reqs:
                req_ctr.inc()
                queue_hist.observe(max((now_s - r.arrival_s) * 1e3, 0.0))
                if r.latency_ms is not None:
                    lat_hist.observe(r.latency_ms)
            if exits is not None:
                for stage, count in enumerate(exits):
                    if count:
                        o.cascade_stage_exits_total.labels(
                            tenant=tid, stage=str(stage)).inc(float(count))
        return reqs


# --------------------------------------------------------------------------- #
# LM serving (prefill + decode)
# --------------------------------------------------------------------------- #
class LMServer:
    """Batch LM text completion over the framework's Model. Greedy decode.

    The decode loop is jit'd once per (batch, max_len); state threads the KV
    cache exactly like the dry-run decode cells, so what the tests exercise
    on CPU is the same program the production mesh lowers.
    """

    def __init__(self, model, params, *, batch: int, max_len: int,
                 kv_quant: bool = False):
        self.model = model
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.kv_quant = kv_quant          # int8 KV cache (paper §5 → decode)
        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))

    def _prefill_fn(self, params, state, tokens):
        """Sequential prefill via decode steps (teacher-forcing the prompt);
        simple and cache-correct for the CPU path."""
        def body(carry, tok):
            st, _ = carry
            logits, st = self.model.decode_step(params, st, tok[:, None])
            return (st, logits.astype(jnp.float32)), None

        (state, logits), _ = jax.lax.scan(body,
                                          (state, jnp.zeros(
                                              (tokens.shape[0],
                                               self.model.cfg.vocab),
                                              jnp.float32)),
                                          tokens.T)
        return state, logits

    def generate(self, prompts: np.ndarray, n_new: int) -> np.ndarray:
        """prompts (B, S) int32 → (B, S + n_new) completed greedily."""
        B, S = prompts.shape
        assert B == self.batch and S + n_new <= self.max_len
        state = self.model.init_decode_state(B, self.max_len,
                                             params=self.params,
                                             kv_quant=self.kv_quant)
        state, logits = self._prefill(self.params, state,
                                      jnp.asarray(prompts))
        out = [np.asarray(prompts)]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for _ in range(n_new):
            out.append(np.asarray(tok)[:, None])
            logits, state = self._decode(self.params, state, tok[:, None])
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return np.concatenate(out, axis=1)
