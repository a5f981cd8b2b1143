"""One caller sending back-to-back ``predict`` calls.

Traffic parameters (``bench/traffic/<name>.json``):

  rows_per_call   rows in each call
  pool_calls      distinct seeded batches the calls cycle through
  check_rows      distinct rows compared with the reference, spread
                  evenly over the pool's batches; must divide each batch
                  into whole slices

The predictor is the one the system's autotuner picks for the call's
batch (``engine_select.choose``); the runtime is bypassed.  Each call
ends in scores on the host.  The window runs whole calls until
``seconds`` have passed; ``rows_per_s`` is every row scored over the
window's whole length.  Every call's answers for its batch's checked
rows are compared, not only one call's.  A seeded permutation cuts the
batch's positions into slices of ``check_rows / pool_calls``; before a
call, the batch's checked rows trade places with the slice that call
reads (call ``i`` reads slice ``i mod slices``), so any ``slices``
calls in a row check every position of a batch, while each batch stays
a permutation of its rows and the reference scores the same rows.
"""
from __future__ import annotations

import time

import numpy as np

import forestgen
from harness import Ctx, Outcome, Setup


class Session:
    def __init__(self, ctx: Ctx, pred, pool: np.ndarray, setup: Setup):
        self.ctx, self.pred, self.pool, self.setup = ctx, pred, pool, setup


def setup(ctx: Ctx) -> Session:
    from repro.core import engine_select
    tr = ctx.traffic
    B, P = tr["rows_per_call"], tr["pool_calls"]
    pool = forestgen.rows(forestgen.rng_for(ctx.seed, 1), P * B,
                          ctx.model.n_features).reshape(P, B, -1)
    forest = ctx.model.program_forest()
    t0 = time.perf_counter()
    choice = engine_select.choose(forest, B, **ctx.choose_kw())
    t1 = time.perf_counter()
    choice.predictor.predict(pool[0])
    t2 = time.perf_counter()
    return Session(ctx, choice.predictor, pool,
                   Setup(choice.engine, t1 - t0, t2 - t1))


def measure(s: Session) -> Outcome:
    ctx, pred, pool = s.ctx, s.pred, s.pool
    P, B = pool.shape[:2]
    per = max(1, ctx.traffic["check_rows"] // P)
    if B % per:
        raise ValueError(f"check_rows / pool_calls = {per} does not "
                         f"divide a {B}-row call")
    n_slices = B // per
    slices = forestgen.rng_for(ctx.seed, 3).permutation(B).reshape(
        n_slices, per)
    at = np.arange(P) % n_slices      # slice holding each batch's checked rows
    rows = pool[np.arange(P)[:, None], slices[at]]
    kept: list = []
    walls: list = []
    failed = 0
    with ctx.window() as win:
        cpu_begin = time.process_time()
        t_begin = time.perf_counter()
        i = 0
        while True:
            p = i % P
            sl = i % n_slices
            if sl != at[p]:
                a, b = slices[at[p]], slices[sl]
                pool[p, np.r_[a, b]] = pool[p, np.r_[b, a]]
                at[p] = sl
            t0 = time.perf_counter()
            try:
                with ctx.span("predict"):
                    y = pred.predict(pool[p])
                kept.append((p, np.asarray(y)[slices[sl]]))
            except Exception:     # noqa: BLE001 — counted as a failed call
                failed += 1
            t1 = time.perf_counter()
            walls.append(t1 - t0)
            i += 1
            if t1 - t_begin >= ctx.seconds:
                break
        t_end = time.perf_counter()
        cpu_s = time.process_time() - cpu_begin
    window_s = t_end - t_begin
    C = ctx.model.cfg["n_classes"]
    got = np.concatenate([y for _, y in kept]) if kept else np.zeros((0, C))
    row_ids = np.concatenate([p * per + np.arange(per) for p, _ in kept]) \
        if kept else np.zeros(0, np.int64)
    return Outcome(
        e2e={"rows_per_s": (i - failed) * B / window_s},
        attempted=i, failed=failed, steps=[(B, w) for w in walls],
        got=got, rows=rows.reshape(P * per, -1),
        row_ids=row_ids, missing=failed * per,
        notes={"calls": i, "rows_per_call": B, "window_s": window_s,
               "compiles_in_window": win.get("compiles"),
               "call_ms_p10_p50_p90": [float(q) for q in np.percentile(
                   np.asarray(walls) * 1e3, [10, 50, 90])],
               "process_cpu_s": cpu_s})
