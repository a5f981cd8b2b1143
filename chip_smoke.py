"""Chip smoke run: drive the forest serving path once on TPU and check it.

    python chip_smoke.py              # one chip: engines, serving, cascade
    python chip_smoke.py --chips 4    # tree-sharded execution on 4 chips

One process, one run.  Every phase raises on a wrong result, so any
failure exits non-zero; so does a machine where JAX finds no TPU, and a
directory that holds this script without the rest of the repository.
Only a run that passed every phase prints its last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip, at the paper's full random-forest size (1024 trees × 64
leaves, the mnist signature: d=784, 10 classes), float and int16:

  engines  every registered engine on both backends — six XLA engines,
           three compiled Pallas kernels — scores a 2048-row batch and
           agrees with ``Forest.predict_oracle`` (bit-exact int16,
           rtol=1e-5 float);
  serving  ``ServingRuntime.from_forests`` autotunes that forest and a
           trained magic random forest, warms up, serves open-loop
           Poisson requests; every score matches the oracle and no
           compile happens after warmup;
  cascade  the fused single-kernel Pallas cascade equals the staged
           loop bit for bit, scores and exit counts;
  wide     the three compiled Pallas kernels at 500 trees × 255 leaves
           (8 mask words a node), d=136, 4096 rows, float32, agree with
           ``Forest.predict_oracle``.

``--chips 4`` runs only the tree-sharded path: 10240 trees × 64 leaves,
d=136, int16, 4096 rows, sharded over four chips against the unsharded
predictor on one, then a few requests through
``ForestServer.from_forest(n_devices=4)``.

Timings printed on the way are set-up and smoke-run numbers, not a
benchmark.  Outputs (the autotune cache) go to ``chiprun_out/chip_smoke``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro import core  # noqa: E402
from repro.compile_cache import setup_compile_cache  # noqa: E402
from repro.core import registry  # noqa: E402

SEED = 0
RTOL, ATOL = 1e-5, 1e-6


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devs) < count:
        raise SystemExit(f"need {count} chips, JAX sees {len(devs)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    log(f"device: {info['kind']} × {info['count']} ({info['platform']})")
    return info


def rf_forest(n_trees: int, n_leaves: int, d: int, n_classes: int):
    """Seeded forest of random-forest shape: unbalanced trees, leaves
    holding class-vote fractions (non-negative, summing to 1)."""
    import dataclasses
    f = core.random_forest_ir(n_trees, n_leaves, d, n_classes=n_classes,
                              seed=SEED, full=False)
    rng = np.random.default_rng(SEED + 1)
    votes = rng.dirichlet(np.ones(n_classes), size=f.leaf_value.shape[:2])
    return dataclasses.replace(f, leaf_value=votes.astype(np.float32))


def q_oracle(qf, X) -> np.ndarray:
    return (qf.predict_oracle(core.quantize_inputs(qf, X))
            / core.leaf_scale(qf)).astype(np.float32)


def check(tag: str, got, ref, exact: bool, atol: float = ATOL) -> None:
    got = np.asarray(got)
    if exact:
        np.testing.assert_array_equal(got, ref, err_msg=tag)
    else:
        np.testing.assert_allclose(got, ref, rtol=RTOL, atol=atol,
                                   err_msg=tag)


def pallas_compiled(pred, X) -> bool:
    """The predictor's program holds a Mosaic kernel (``tpu_custom_call``),
    i.e. the kernel compiled rather than ran in the interpreter."""
    import jax.numpy as jnp
    from repro.kernels.ops import bucket_rows
    rows = bucket_rows(X.shape[0], pred.block_b)
    x = jnp.zeros((rows, max(X.shape[1], 1)), jnp.float32)
    return "tpu_custom_call" in pred._fn.lower(x).as_text()


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #
def run_all(steps) -> None:
    """Run every (name, step); report each failure with its traceback and
    raise once at the end, so one run shows every fault."""
    failed = []
    for name, step in steps:
        t0 = time.perf_counter()
        try:
            step()
        except Exception:                    # noqa: BLE001 — re-raised below
            traceback.print_exc()
            failed.append(name)
            log(f"{name}: FAILED")
        else:
            log(f"{name}: passed ({time.perf_counter() - t0:.1f} s)")
    if failed:
        raise RuntimeError(f"failed: {', '.join(failed)}")


def phase_engines(forest, qf, X) -> None:
    ref_f = forest.predict_oracle(X)
    ref_q = q_oracle(qf, X)

    def engine(spec, f, ref, exact):
        pred = core.compile_forest(f, engine=spec.name, backend=spec.backend)
        check(spec.tune_name, pred.predict(X), ref, exact)
        if spec.backend == "pallas" and not pallas_compiled(pred, X):
            raise AssertionError(f"{spec.tune_name}: kernel not compiled")

    run_all([(f"engine {spec.tune_name}/{tag}",
              lambda spec=spec, f=f, ref=ref, exact=exact:
              engine(spec, f, ref, exact))
             for spec in registry.specs()
             for tag, f, ref, exact in (("f32", forest, ref_f, False),
                                        ("int16", qf, ref_q, True))])


def phase_serving(qf, X) -> None:
    from repro.data import datasets
    from repro.inference import ServingRuntime
    from repro.trees.random_forest import RandomForest, RandomForestConfig

    ds = datasets.load("magic")
    rf = RandomForest(RandomForestConfig(n_trees=128, max_leaves=32,
                                         seed=SEED)).fit(ds.X_train,
                                                         ds.y_train)
    magic = core.from_random_forest(rf)
    rows = {"mnist_rf_int16": X, "magic_rf": ds.X_test.astype(np.float32)}
    refs = {"mnist_rf_int16": q_oracle(qf, X),
            "magic_rf": magic.predict_oracle(rows["magic_rf"])}
    t0 = time.perf_counter()
    rt = ServingRuntime.from_forests(
        {"mnist_rf_int16": qf, "magic_rf": magic},
        cache_path=os.path.join(OUT, "engine_cache.json"))
    for tid in rt.model_ids:
        choice = rt.tenant(tid).engine_choice
        pallas = [c for c in choice.timings if c.startswith("pallas-")]
        if not pallas:
            raise AssertionError(f"{tid}: autotune saw no Pallas candidate")
        log(f"serving {tid}: engine {choice.engine} "
            f"(of {len(choice.timings)} candidates, {len(pallas)} Pallas)")
    rt.warmup()
    log(f"serving: autotune + warmup {time.perf_counter() - t0:.1f} s")

    n, rate = 400, 1000.0
    rng = np.random.default_rng(SEED)
    tids = rng.choice(list(rt.model_ids), size=n)
    idx = [int(rng.integers(0, rows[t].shape[0])) for t in tids]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    reqs = []
    with rt:
        base = time.perf_counter() + 0.005
        for tid, i, at in zip(tids, idx, arrivals):
            while time.perf_counter() < base + at:
                time.sleep(min(max(base + at - time.perf_counter(), 0.0),
                               5e-4))
            reqs.append(rt.submit(tid, rows[tid][i], arrival_s=base + at))
        results = [r.wait(timeout=120) for r in reqs]
    for tid, i, got in zip(tids, idx, results):
        check(f"serving {tid} row {i}", got[None], refs[tid][i][None],
              exact=tid == "mnist_rf_int16")
    for tid in rt.model_ids:
        anomalies = rt.stats(tid)["retrace_anomalies"]
        if anomalies:
            raise AssertionError(f"{tid}: {anomalies} retraces after warmup")
    lat = np.array([r.latency_ms for r in reqs])
    log(f"serving: {n} requests at {rate:g}/s open loop, all scores match "
        f"the oracle, 0 retraces after warmup; smoke-run latency "
        f"p50 {np.percentile(lat, 50):.3f} ms, "
        f"p99 {np.percentile(lat, 99):.3f} ms (not a benchmark)")


def phase_cascade(qf, X) -> None:
    from repro.cascade import (CascadeSpec, FusedCascadePredictor,
                               MarginGate)
    from repro.core.registry import normalize_scores, votes_mode

    stages = (qf.n_trees // 4, qf.n_trees)
    staged = core.compile_forest(
        qf, engine="bitvector", backend="pallas",
        cascade=CascadeSpec(stages, MarginGate(np.inf)))
    # gate at the median stage-0 margin, so about half the rows exit
    p = np.sort(normalize_scores(staged.cumulative_scores(X)[0],
                                 votes=votes_mode(qf)), axis=1)
    gate = MarginGate(float(np.median(p[:, -1] - p[:, -2])))
    staged.set_policy(gate)
    fused = core.compile_forest(
        qf, engine="bitvector", backend="pallas",
        cascade=CascadeSpec(stages, gate, fused=True))
    if not (isinstance(fused, FusedCascadePredictor) and fused._use_kernel):
        raise AssertionError("fused cascade did not take the kernel tier")
    want = staged.predict(X)
    check("cascade scores", fused.predict(X), want, exact=True)
    np.testing.assert_array_equal(fused.last_exit_counts,
                                  staged.last_exit_counts,
                                  err_msg="cascade exit counts")
    exits = fused.last_exit_counts
    if not 0 < exits[0] < X.shape[0]:
        raise AssertionError(f"gate never split the batch: exits {exits}")
    log(f"cascade: fused Pallas kernel == staged loop bit for bit, "
        f"exits per stage {exits.tolist()}")


def phase_wide(T=500, L=255, d=136, B=4096) -> None:
    """The Pallas kernels on trees wider than 64 leaves, at the shape of
    LightGBM's published ranker (num_leaves 255, 500 iterations, the 136
    MSLR-WEB30K features), leaves N(0, 0.1^2) as a boosting step's."""
    import dataclasses
    f = core.random_forest_ir(T, L, d, n_classes=1, seed=SEED, full=False)
    f = dataclasses.replace(f, leaf_value=0.1 * f.leaf_value)
    X = np.random.default_rng(SEED).standard_normal((B, d), dtype=np.float32)
    ref = f.predict_oracle(X)
    # float32 sums of T leaves in another order than the float64 oracle:
    # each addition rounds by at most 2^-24 of a partial sum, and a
    # partial sum is below the sum over trees of each tree's largest leaf
    top = np.abs(f.leaf_value).max(axis=(1, 2))
    atol = T * 2.0 ** -24 * float(top.sum())
    log(f"wide: {T} trees × {L} leaves ({f.n_words} mask words), d={d}, "
        f"max depth {f.max_depth}; batch {B}; atol {atol:.3g}")

    def engine(spec):
        pred = core.compile_forest(f, engine=spec.name, backend=spec.backend)
        check(f"wide {spec.tune_name}", pred.predict(X), ref, False, atol)
        if not pallas_compiled(pred, X):
            raise AssertionError(f"{spec.tune_name}: kernel not compiled")

    run_all([(f"wide {spec.tune_name}", lambda spec=spec: engine(spec))
             for spec in registry.specs("pallas")])


def one_chip(T=1024, L=64, d=784, C=10, B=2048) -> None:
    forest = rf_forest(T, L, d, C)
    X = np.random.default_rng(SEED).normal(size=(B, d)).astype(np.float32)
    qf = core.quantize_forest(forest, X)
    log(f"forest: {T} trees × {L} leaves, d={d}, C={C}, "
        f"max depth {forest.max_depth}; batch {B}")
    run_all([("phase engines", lambda: phase_engines(forest, qf, X)),
             ("phase serving", lambda: phase_serving(qf, X)),
             ("phase cascade", lambda: phase_cascade(qf, X)),
             ("phase wide trees", phase_wide)])


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #
def four_chips(T=10240, L=64, d=136, B=4096, D=4) -> None:
    from repro.core import shard
    from repro.inference.server import ForestServer

    X = np.random.default_rng(SEED).normal(size=(B, d)).astype(np.float32)
    qf = core.quantize_forest(
        core.random_forest_ir(T, L, d, n_classes=1, seed=SEED, full=False),
        X)
    log(f"forest: {T} trees × {L} leaves, d={d}, int16; batch {B}")
    t0 = time.perf_counter()
    single = core.compile_forest(qf, engine="bitmm")
    want = single.predict(X)
    sharded = shard.tree_sharded(qf, "bitmm", n_devices=D)
    spans = {k: len(a.sharding.device_set)
             for k, a in {**sharded._sharded, **sharded._repl}.items()}
    if set(spans.values()) != {D}:
        raise AssertionError(f"arrays do not span {D} devices: {spans}")
    check("tree-sharded vs one chip", sharded.predict(X), want, exact=True)
    check("one chip vs oracle", want, q_oracle(qf, X), exact=True)
    log(f"sharded: bitmm over {D} chips == one chip == oracle, bit for bit; "
        f"arrays span {D} devices ({time.perf_counter() - t0:.1f} s)")

    srv = ForestServer.from_forest(
        qf, n_devices=D, engines=("qs-bitmm",),
        cache_path=os.path.join(OUT, "engine_cache.json"))
    rows = np.random.default_rng(SEED + 2).integers(0, B, size=64)
    t = time.perf_counter()
    reqs = [srv.submit(X[i], arrival_s=t + k * 1e-3)
            for k, i in enumerate(rows)]
    srv.flush(now_s=t + 1.0)
    check("ForestServer(n_devices=4)",
          np.stack([r.result for r in reqs]), want[rows], exact=True)
    log(f"server: {len(reqs)} requests through ForestServer.from_forest("
        f"n_devices={D}) match the one-chip scores")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tree-sharded four-chip path")
    args = ap.parse_args(argv)
    info = require_tpu(args.chips)
    log(f"compile cache: {setup_compile_cache()}")
    os.makedirs(OUT, exist_ok=True)
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
