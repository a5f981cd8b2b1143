"""Benchmark driver: one function per paper table/figure + the TPU
roofline benches + the engine A/B harness.

    PYTHONPATH=src python -m benchmarks.run            # default scale
    REPRO_BENCH_SCALE=quick  python -m benchmarks.run  # CI-sized
    REPRO_BENCH_SCALE=full   python -m benchmarks.run  # paper-sized (hours)
    PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_engines.json
    PYTHONPATH=src python -m benchmarks.run --only table4_merging

``--json`` makes the engine bench write ``BENCH_engines.json``, the
cascade bench ``BENCH_cascade.json``, the optimizer bench
``BENCH_optim.json``, and the autotune bench ``BENCH_autotune.json``
perf snapshots at the repo root, so successive PRs accumulate a
trajectory.  ``--only <name>`` runs a single bench — the
full sweep is far too slow when iterating on one table.

The forest-roofline bench is a dry run on 512 placeholder host
devices, so it runs as a CPU-only subprocess (``JAX_PLATFORMS=cpu``):
on a chip machine this process holds the chip, and a child that
reached for it would fail or hang.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from repro.compile_cache import setup_compile_cache

from .common import SCALE


def _run_roofline() -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.roofline_forest"],
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."))
    if r.returncode != 0:
        print("[bench] roofline_forest FAILED", file=sys.stderr)
        sys.exit(1)


def _benches(json_flag: bool) -> dict:
    """name → zero-arg runner, in sweep order.  Lazy imports so
    ``--only x`` never pays for (or breaks on) the other benches."""
    def table(name):
        def run():
            import importlib
            importlib.import_module(f"benchmarks.{name}").main()
        return run

    def with_json(name):
        def run():
            import importlib
            importlib.import_module(f"benchmarks.{name}").main(
                ["--json"] if json_flag else [])
        return run

    return {
        "table2_ranking": table("table2_ranking"),
        "table3_quant_accuracy": table("table3_quant_accuracy"),
        "table4_merging": table("table4_merging"),
        "table5_classification": table("table5_classification"),
        "fig1_speedup": table("fig1_speedup"),
        "bench_coldstart": table("bench_coldstart"),
        "bench_engines": with_json("bench_engines"),
        "bench_cascade": with_json("bench_cascade"),
        "bench_optim": with_json("bench_optim"),
        "bench_serving": with_json("bench_serving"),
        "bench_autotune": with_json("bench_autotune"),
        "roofline_forest": _run_roofline,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write the BENCH_*.json perf snapshots")
    ap.add_argument("--only", default=None,
                    help="run a single bench by name")
    args = ap.parse_args()

    benches = _benches(args.json)
    if args.only is not None and args.only not in benches:
        ap.error(f"unknown bench {args.only!r}; choose from "
                 f"{sorted(benches)}")

    setup_compile_cache()
    t0 = time.time()
    print(f"[bench] scale={SCALE}")
    selected = {args.only: benches[args.only]} if args.only else benches
    for name, run in selected.items():
        t = time.time()
        print(f"\n[bench] running {name} ...", flush=True)
        run()
        print(f"[bench] {name} done in {time.time()-t:.1f}s", flush=True)

    print(f"\n[bench] all done in {time.time()-t0:.1f}s; CSVs in "
          "experiments/bench/")


if __name__ == "__main__":
    main()
