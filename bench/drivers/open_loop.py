"""Open-loop requests through the serving runtime.

Traffic parameters (``bench/traffic/<name>.json``):

  rate_per_s        requests offered per second
  rows_per_request  {"kind": "fixed", "rows": k} or
                    {"kind": "lognormal", "mean": m, "sigma": s, "cap": c}
  pool_rows         distinct seeded rows the requests draw from
  check_rows        rows of served requests compared with the reference
  max_batch, max_wait_ms   the runtime's batching

A window of ``S`` seconds offers ``round(rate · S)`` requests at
uniformly drawn times (a Poisson process given its count), each of a
size from the same fixed multiset for every seed, in a seeded order.
Every row of a request is submitted at the request's scheduled arrival
and stamped with it, so a driver that lags inflates latency instead of
thinning the load (after ``benchmarks/bench_serving.py``'s
``_open_loop``).  A request's latency runs from its scheduled arrival
to the moment its last score is handed to the client's future.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

import forestgen
from harness import Ctx, Outcome, Setup, wait_until

DRAIN_S = 60.0          # how long past the window answers are waited for


def sizes(spec: dict, n: int) -> np.ndarray:
    """Rows per request: the same multiset of ``n`` sizes for any seed
    (quantiles of the distribution), in sorted order."""
    if spec["kind"] == "fixed":
        return np.full(n, int(spec["rows"]), np.int64)
    if spec["kind"] == "lognormal":
        sigma = float(spec["sigma"])
        law = statistics.NormalDist(np.log(spec["mean"]) - sigma ** 2 / 2,
                                    sigma)
        q = (np.arange(n) + 0.5) / n
        k = np.exp([law.inv_cdf(p) for p in q])
        return np.clip(np.rint(k), 1, int(spec["cap"])).astype(np.int64)
    raise ValueError(f"unknown rows_per_request kind {spec['kind']!r}")


def schedule(traffic: dict, seed: int, seconds: float):
    """``(arrival offsets, rows per request, first pool row)``."""
    rng = forestgen.rng_for(seed, 2)
    n = max(1, round(traffic["rate_per_s"] * seconds))
    at = np.sort(rng.random(n)) * seconds
    k = rng.permutation(sizes(traffic["rows_per_request"], n))
    start = rng.integers(0, traffic["pool_rows"], n)
    return at, k, start


def check_sample(k: np.ndarray, seed: int, budget: int) -> np.ndarray:
    """Requests whose answers are compared: the longest request, then
    requests in a seeded order while they fit ``budget`` rows."""
    rng = forestgen.rng_for(seed, 3)
    order = [int(np.argmax(k))] + [int(i) for i in rng.permutation(len(k))
                                   if i != np.argmax(k)]
    out, used = [], 0
    for i in order:
        if used + k[i] > budget and out:
            continue
        out.append(i)
        used += int(k[i])
        if used >= budget:
            break
    return np.array(sorted(out), np.int64)


class Session:
    def __init__(self, ctx: Ctx, runtime, pool: np.ndarray, setup: Setup):
        self.ctx, self.runtime, self.pool, self.setup = ctx, runtime, pool, setup


class _Spanned:
    """The tenant's predictor with a ``bench.predict`` host span around
    each call (traced runs only), so the trace shows the worker's
    predictor calls."""

    def __init__(self, pred, ctx: Ctx):
        self._pred, self._ctx = pred, ctx

    def predict(self, X):
        with self._ctx.span("predict"):
            return self._pred.predict(X)

    def __getattr__(self, name):
        return getattr(self._pred, name)


def setup(ctx: Ctx) -> Session:
    from repro.inference import ServingRuntime
    tr = ctx.traffic
    pool = forestgen.rows(forestgen.rng_for(ctx.seed, 1), tr["pool_rows"],
                          ctx.model.n_features)
    forest = ctx.model.program_forest()
    t0 = time.perf_counter()
    rt = ServingRuntime.from_forests(
        {ctx.name: forest}, max_batch=tr["max_batch"],
        max_wait_ms=tr["max_wait_ms"], **ctx.choose_kw())
    t1 = time.perf_counter()
    rt.warmup()
    t2 = time.perf_counter()
    tenant = rt.tenant(ctx.name)
    if ctx.tracing:
        tenant.predictor = _Spanned(tenant.predictor, ctx)
    return Session(ctx, rt, pool,
                   Setup(tenant.engine_choice.engine, t1 - t0, t2 - t1))


def measure(s: Session) -> Outcome:
    ctx, rt, pool = s.ctx, s.runtime, s.pool
    P = len(pool)
    at, k, start = schedule(ctx.traffic, ctx.seed, ctx.seconds)
    n = len(at)
    first = np.concatenate([[0], np.cumsum(k)[:-1]])
    done = np.full(int(k.sum()), np.nan)
    reqs = [None] * int(k.sum())
    lag = np.zeros(n)

    def stamp(j):
        return lambda _f: done.__setitem__(j, time.perf_counter())

    with ctx.window() as win:
        with rt:
            base = time.perf_counter() + 0.01
            for i in range(n):
                due = base + at[i]
                with ctx.span("wait"):
                    wait_until(due)
                lag[i] = time.perf_counter() - due
                with ctx.span("submit"):
                    for r in range(k[i]):
                        j = first[i] + r
                        req = rt.submit(ctx.name, pool[(start[i] + r) % P],
                                        arrival_s=due)
                        req.future.add_done_callback(stamp(j))
                        reqs[j] = req
            closed = base + ctx.seconds
            with ctx.span("drain"):
                deadline = closed + DRAIN_S
                for req in reqs:
                    try:
                        req.future.exception(
                            timeout=max(deadline - time.perf_counter(), 0))
                    except TimeoutError:
                        break
        end = time.perf_counter()
    stats = rt.stats(ctx.name)
    bad = np.array([not r.future.done() or r.future.exception() is not None
                    for r in reqs])
    done[bad] = closed + DRAIN_S          # a failed request misses any limit
    last = np.maximum.reduceat(done, first) if n else np.zeros(0)
    lat_ms = (last - (base + at)) * 1e3
    failed = int(np.maximum.reduceat(bad.astype(np.int64), first).sum())

    check = check_sample(k, ctx.seed, ctx.traffic["check_rows"])
    idx = np.concatenate([first[i] + np.arange(k[i]) for i in check])
    pool_idx = np.concatenate([(start[i] + np.arange(k[i])) % P
                               for i in check])
    ok = ~bad[idx]
    got = np.stack([reqs[j].future.result() for j in idx[ok]]) \
        if ok.any() else np.zeros((0, ctx.model.cfg["n_classes"]))
    queue, steps = [], {}
    for r in reqs:
        if r.span is not None:
            queue.append(r.span.phases["queue_ms"])
            ph = r.span.phases
            steps.setdefault(r.done_s, (
                r.span.batch_size,
                (ph["form_ms"] + ph["pad_ms"] + ph["compute_ms"]
                 + ph["sync_ms"]) * 1e-3))
    finite = lat_ms[np.isfinite(lat_ms)]
    e2e = {"p50_ms": float(np.percentile(lat_ms, 50)),
           "p99_ms": float(np.percentile(lat_ms, 99))}
    return Outcome(
        e2e=e2e, attempted=n, failed=failed,
        steps=list(steps.values()), got=got, rows=pool[pool_idx[ok]],
        row_ids=np.arange(int(ok.sum())),
        missing=int((~ok).sum()), queue_ms=np.asarray(queue),
        latency_ms=lat_ms,
        counters={"n_requests": stats["n_requests"],
                  "n_batches": stats["n_batches"],
                  "retraces_after_warmup": stats.get("retrace_anomalies")},
        notes={"requests": n, "rows": int(k.sum()),
               "offered_per_s": ctx.traffic["rate_per_s"],
               "lag_p99_ms": float(np.percentile(lag, 99) * 1e3),
               "lag_max_ms": float(lag.max() * 1e3),
               "drain_s": end - closed,
               "latency_max_ms": float(finite.max()) if finite.size
               else None,
               "compiles_in_window": win.get("compiles")})
