"""Find an open-loop cell's knee: the highest offered rate at which the
runtime's queue does not grow over the window.

    python bench/knee.py --workload mnist_rf.online --rates 2000,4000,8000 \
        --seconds 10 [--seed 1]

One process builds and warms the cell once, then offers each rate in
turn to a fresh runtime over the same warmed predictor, and prints one
JSON line per rate: p50/p99 latency, completions per second, the p50 of
the window's last quarter over its first quarter (about 1 while the
queue holds, growing with the run when it does not), and how long
after the window the last answer came.  The ladder stops at the first
rate whose last answer came more than a second after the window.  The
last line names the knee: the highest rate with no failed request, its
last answer within 0.25 s of the window's close and its last quarter's
p50 under twice its first quarter's.  The rate written into a cell's
traffic file is about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.steady_malloc()
    import jax

    import cellmodel
    from harness import Ctx
    from repro.inference import ServingRuntime

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, cfg, traffic = run.cell_spec(args.workload, bench)
    device = run.require_chip(cell["chips"])
    run.setup_jax(run.CACHE)
    driver = run.load_module("drivers", traffic["driver"])
    ctx = Ctx(args.workload, cellmodel.build(cfg), traffic, args.seed,
              args.seconds, False, run.CACHE, device["kind"])
    jax.monitoring.register_event_duration_secs_listener(ctx.on_event)
    s = driver.setup(ctx)
    pred = s.runtime.tenant(args.workload).predictor
    print(json.dumps({"engine": s.setup.engine, "device": device}),
          flush=True)
    held = []
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.traffic = dict(traffic, rate_per_s=rate)
        s.runtime = ServingRuntime()
        s.runtime.add_model(args.workload, pred,
                            max_batch=traffic["max_batch"],
                            max_wait_ms=traffic["max_wait_ms"])
        s.runtime.warmup()
        t0 = time.perf_counter()
        out = driver.measure(s)
        lat = out.latency_ms
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, "p50_ms": out.e2e["p50_ms"],
            "p99_ms": out.e2e["p99_ms"],
            "late_over_early_p50": float(np.median(lat[-q:])
                                         / np.median(lat[:q])),
            "drain_s": out.notes["drain_s"],
            "lag_p99_ms": out.notes["lag_p99_ms"],
            "batch_rows": out.counters["n_requests"]
            / max(out.counters["n_batches"], 1),
            "failed": out.failed,
            "compiles_in_window": out.notes["compiles_in_window"],
            "wall_s": time.perf_counter() - t0}), flush=True)
        if out.failed == 0 and out.notes["drain_s"] < 0.25 and \
                np.median(lat[-q:]) < 2 * np.median(lat[:q]):
            held.append(rate)
        if out.notes["drain_s"] > 1.0:
            break                   # the queue grew: higher rates only more
    knee = max(held, default=None)
    print(json.dumps({"knee_per_s": knee,
                      "four_fifths_per_s": knee and round(0.8 * knee)}))


if __name__ == "__main__":
    main()
