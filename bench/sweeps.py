"""Repeat the system's engine selection for a cell from scratch, to see
whether its pick is stable.

    python bench/sweeps.py --workload msn_gbt.batch --repeats 3

Each repeat is a fresh full sweep (``engine_select.choose`` with
``force=True`` and no cache file) over the candidates the configuration
allows, at the batch the cell's traffic uses; one JSON line per repeat
with the winner and every candidate's time.  Where repeats disagree, the
configuration pins its engine (``"engines"`` in its file).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    import cellmodel
    from repro.core import engine_select

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, cfg, traffic = run.cell_spec(args.workload, bench)
    run.require_chip(cell["chips"])
    run.setup_jax(run.CACHE)
    forest = cellmodel.build(cfg).program_forest()
    batch = traffic.get("rows_per_call") or traffic["max_batch"]
    kw = {"engines": tuple(cfg["engines"])} if cfg.get("engines") else {}
    for k in range(args.repeats):
        t0 = time.perf_counter()
        ch = engine_select.choose(forest, batch, cache_path=None,
                                  force=True, **kw)
        print(json.dumps({"repeat": k, "batch": batch, "winner": ch.engine,
                          "sweep_s": time.perf_counter() - t0,
                          "timings_s": ch.timings}), flush=True)


if __name__ == "__main__":
    main()
