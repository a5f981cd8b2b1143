"""Serving driver: tree-ensemble scoring or LM generation.

    # forest serving (the paper's workload)
    PYTHONPATH=src python -m repro.launch.serve --mode forest \
        --engine rapidscorer --quantize --n-requests 2000

    # concurrent multi-tenant runtime (threaded, adaptive batching)
    PYTHONPATH=src python -m repro.launch.serve --mode runtime \
        --tenants 2 --quantize --slo-p99-ms 10 --n-requests 2000

    # LM generation (reduced config on CPU)
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --arch smollm_360m --reduced --n-new 16

``--mode runtime`` drives ``repro.inference.runtime.ServingRuntime``
(docs/SERVING.md): N tenants hot in one process, shape-warmed, served by
the worker thread under open-loop Poisson arrivals; ``--slo-p99-ms``
attaches the adaptive batching controller, ``--save-fleet``/
``--load-fleet`` round-trip the whole fleet through packed artifacts.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import core
from ..compile_cache import setup_compile_cache
from ..configs import get_config
from ..data import datasets
from ..inference.server import ForestServer, LMServer
from ..models.model import Model
from ..obs.log import get_logger
from ..trees.random_forest import RandomForest, RandomForestConfig

log = get_logger("serve")


def _cascade_spec(args):
    """--cascade "16,64" [--cascade-policy margin|proba|bound
    --cascade-threshold t] → CascadeSpec (None when --cascade unset)."""
    if not args.cascade:
        return None
    from ..cascade import CascadeSpec, MarginGate, ProbaGate, ScoreBoundGate
    stages = tuple(int(s) for s in args.cascade.split(","))
    t = args.cascade_threshold
    policy = {"margin": lambda: MarginGate(t if t is not None else 0.9),
              "proba": lambda: ProbaGate(t if t is not None else 0.95),
              "bound": lambda: ScoreBoundGate(t if t is not None else 0.0),
              }[args.cascade_policy]()
    return CascadeSpec(stages=stages, policy=policy)


def serve_forest(args) -> dict:
    ds = datasets.load(args.dataset)
    rf = RandomForest(RandomForestConfig(
        n_trees=args.n_trees, max_leaves=args.n_leaves,
        seed=args.seed)).fit(ds.X_train, ds.y_train)
    forest = core.from_random_forest(rf)
    if args.quantize:
        forest = core.quantize_forest(forest, ds.X_train)
    pred = core.compile_forest(forest, engine=args.engine,
                               backend=args.backend,
                               cascade=_cascade_spec(args))

    mserver = None
    if args.metrics_port is not None:
        from ..obs.expo import MetricsServer
        from ..obs.metrics import get_registry
        server = ForestServer(pred, max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms, obs=True)
        mserver = MetricsServer(get_registry(),
                                extra=server.stats.summary,
                                port=args.metrics_port).start()
        log.info("metrics_endpoint", url=mserver.url)
    else:
        server = ForestServer(pred, max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms)
    rng = np.random.default_rng(args.seed)
    rows = rng.integers(0, ds.X_test.shape[0], size=args.n_requests)

    # Poisson arrivals; virtual clock so results are deterministic
    inter = rng.exponential(1.0 / args.rate, size=args.n_requests)
    arrivals = np.cumsum(inter)
    t_start = time.time()
    done = 0
    correct = 0
    for i, (row, at) in enumerate(zip(rows, arrivals)):
        req = server.submit(ds.X_test[row], arrival_s=t_start + at)
        req.label = ds.y_test[row]
        for r in server.poll(now_s=t_start + at):
            done += 1
            if int(np.argmax(r.result)) == int(r.label):
                correct += 1
    for r in server.flush():
        done += 1
        if int(np.argmax(r.result)) == int(r.label):
            correct += 1
    out = server.stats.summary()
    out.update({"engine": args.engine, "backend": args.backend,
                "quantized": bool(args.quantize),
                "accuracy": correct / max(done, 1),
                "wall_s": round(time.time() - t_start, 2)})
    if mserver is not None:
        out["metrics_url"] = mserver.url
        mserver.close()
    if args.cascade:
        out["cascade"] = pred.describe()
        out["mean_trees_evaluated"] = pred.mean_trees_evaluated
    return out


def serve_runtime(args) -> dict:
    """Concurrent multi-tenant serving: threaded runtime, real clock."""
    from ..inference import ServingRuntime, SLOConfig

    slo = SLOConfig(target_p99_ms=args.slo_p99_ms) \
        if args.slo_p99_ms is not None else None
    if args.load_fleet:
        rt = ServingRuntime.load(args.load_fleet)
    else:
        ds = datasets.load(args.dataset)
        rt = ServingRuntime()
        for i in range(args.tenants):
            rf = RandomForest(RandomForestConfig(
                n_trees=args.n_trees, max_leaves=args.n_leaves,
                seed=args.seed + i)).fit(ds.X_train, ds.y_train)
            forest = core.from_random_forest(rf)
            if args.quantize:
                forest = core.quantize_forest(forest, ds.X_train)
            rt.add_model(f"t{i}", core.compile_forest(
                forest, engine=args.engine, backend=args.backend,
                cascade=_cascade_spec(args)),
                max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
                slo=slo)
        if args.save_fleet:
            log.info("fleet_saved", manifest=rt.save(args.save_fleet))
    metrics_url = None
    if args.metrics_port is not None:
        metrics_url = rt.serve_metrics(port=args.metrics_port).url
    warmed = rt.warmup() if args.warmup else {}

    ds = datasets.load(args.dataset)
    rng = np.random.default_rng(args.seed)
    rows = rng.integers(0, ds.X_test.shape[0], size=args.n_requests)
    tids = rng.choice(list(rt.model_ids), size=args.n_requests)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                         size=args.n_requests))
    t_wall = time.time()
    base = time.perf_counter() + 0.005
    reqs = []
    with rt:
        for row, tid, at in zip(rows, tids, arrivals):
            target = base + at
            while time.perf_counter() < target:
                time.sleep(min(max(target - time.perf_counter(), 0.0),
                               5e-4))
            reqs.append(rt.submit(tid, ds.X_test[row], arrival_s=target))
        for r in reqs:
            r.wait(timeout=120)
    lats = np.array([r.latency_ms for r in reqs])
    correct = sum(int(np.argmax(r.result)) == int(ds.y_test[row])
                  for row, r in zip(rows, reqs))
    return {
        "tenants": {tid: rt.stats(tid) for tid in rt.model_ids},
        "warmed": warmed,
        "metrics_url": metrics_url,
        "adaptive": slo is not None,
        "n_requests": len(reqs),
        "rate": args.rate,
        "p50_ms": float(np.percentile(lats, 50)),
        "p99_ms": float(np.percentile(lats, 99)),
        "accuracy": correct / max(len(reqs), 1),
        "wall_s": round(time.time() - t_wall, 2),
    }


def serve_lm(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, q_chunk=64, ssd_chunk=32, loss_chunk=64, remat=False)
    params = model.init_params(jax.random.PRNGKey(args.seed), jnp.float32)
    B, S = args.batch, args.prompt_len
    server = LMServer(model, params, batch=B, max_len=S + args.n_new + 1)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, size=(B, S)).astype(np.int32)
    t0 = time.time()
    out = server.generate(prompts, args.n_new)
    dt = time.time() - t0
    return {"arch": cfg.name, "batch": B, "prompt_len": S,
            "n_new": args.n_new, "out_shape": list(out.shape),
            "tokens_per_s": round(B * args.n_new / dt, 2),
            "wall_s": round(dt, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="forest",
                    choices=["forest", "runtime", "lm"])
    # forest args
    ap.add_argument("--dataset", default="magic")
    ap.add_argument("--engine", default="bitvector",
                    choices=list(core.ENGINES))
    ap.add_argument("--backend", default="jax", choices=["jax", "pallas"])
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--cascade", type=str, default=None,
                    help="comma-separated stage boundaries (tree prefixes),"
                         " e.g. '16,64' — serve a confidence-gated cascade")
    ap.add_argument("--cascade-policy", default="margin",
                    choices=["margin", "proba", "bound"])
    ap.add_argument("--cascade-threshold", type=float, default=None,
                    help="gate threshold (margin/proba) or slack (bound); "
                         "default per policy")
    ap.add_argument("--n-trees", type=int, default=128)
    ap.add_argument("--n-leaves", type=int, default=32)
    ap.add_argument("--n-requests", type=int, default=1000)
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="arrival rate (req/s, virtual clock)")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    # runtime args
    ap.add_argument("--tenants", type=int, default=2,
                    help="runtime mode: number of hot models")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="attach the adaptive batching controller with "
                         "this p99 latency budget")
    ap.add_argument("--no-warmup", dest="warmup", action="store_false",
                    help="skip shape warmup (first requests pay compiles)")
    ap.add_argument("--save-fleet", type=str, default=None,
                    help="persist the fleet as packed artifacts + manifest")
    ap.add_argument("--load-fleet", type=str, default=None,
                    help="cold-start the fleet from a saved manifest")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the observability scrape endpoint "
                         "(/metrics Prometheus text, /metrics.json, "
                         "/traces — docs/OBSERVABILITY.md) on this "
                         "port; 0 picks an ephemeral port")
    # lm args
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--n-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    setup_compile_cache()
    out = {"forest": serve_forest, "runtime": serve_runtime,
           "lm": serve_lm}[args.mode](args)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
