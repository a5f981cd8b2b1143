"""Readings that set a cell's limits: the program's compared numbers and
its control's, on the same checked rows, over several seeds.

    python bench/control.py --workload mnist_rf.online --seeds 1,2,3 \
        --seconds 5

One process runs the cell's timed path for each seed (a short window at
the cell's own load), compares what it served with the reference as a
benchmark run does, and reads the control on the very rows that were
compared.  The control is one precision below the configuration's:

  * fixed point (int16): the system's own int8 path — the same engine
    built on the forest quantized to 8 bits, reading the same rows;
  * float32: the reference computed in bfloat16 (``reference.bf16_scores``).

One JSON line per seed: ``{"seed", "program": {...}, "control": {...}}``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import reference  # noqa: E402
import run  # noqa: E402


def control_scores(m: run.Measured) -> np.ndarray:
    """The control's scores for the distinct checked rows of a run."""
    model, rows = m.model, m.out.rows
    if model.grid is None:
        return reference.bf16_scores(model.trees, rows)
    from repro.core import registry
    spec = registry.by_tune_name(m.ctx.setup.engine.split("@")[0])
    low = registry.build(model.program_forest(bits=8), spec.name,
                         spec.backend)
    return np.asarray(low.predict(rows))


def readings(m: run.Measured) -> dict:
    ref = m.model.reference(m.out.rows)
    ids = m.out.row_ids
    res = run.checks(m.model, m.out, ref)
    return {"program": m.model.numbers(m.out.got, ref[ids]),
            "control": m.model.numbers(control_scores(m)[ids], ref[ids]),
            "checked": int(len(ids)),
            "correct": run.passed(res) and m.out.failed == 0}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        m = run.measure_cell(args.workload, seed, args.seconds, False)
        print(json.dumps(dict(readings(m), seed=seed,
                              engine=m.ctx.setup.engine,
                              failed=m.out.failed)), flush=True)


if __name__ == "__main__":
    main()
