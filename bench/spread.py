"""Run a cell several times, each run its own process, and report each
metric's spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python bench/spread.py --workload msn_gbt.batch --seeds 1,2,3,4,5,6 \
        --sets 2 --seconds 20 [--trace 0] [--out chiprun_out/spread.jsonl]

Each set runs every seed once, in order; the sets repeat the same seeds.
This process never touches JAX, so each child has the chip to itself.
Every result line goes to ``--out``; the summary is the last line.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def parse(lines: list) -> dict:
    """The JSON object on the one line of ``lines``, else ``{}``."""
    try:
        return json.loads(lines[0]) if lines else {}
    except ValueError:
        return {}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    sets: list = []
    for k in range(args.sets):
        lines = []
        for seed in seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            out_lines = p.stdout.strip().splitlines()
            line, notes = parse(out_lines[-1:]), parse(out_lines[-2:-1])
            rec = {"set": k, "seed": seed, "rc": p.returncode,
                   "wall_s": time.perf_counter() - t0, "line": line,
                   "notes": notes.get("run", {})}
            if p.returncode or not line:
                rec["stderr"] = p.stderr[-3000:]
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
            lines.append(line)
        sets.append(lines)
    summary = {"workload": args.workload, "sets": []}
    for lines in sets:
        ms = {}
        for line in lines:
            for name, m in line.get("metrics", {}).items():
                ms.setdefault(name, []).append(m["value"])
        summary["sets"].append({
            name: {"median": statistics.median(v), "spread": spread(v),
                   "first": v[0]} for name, v in ms.items()})
        summary["sets"][-1]["correct"] = sum(bool(x.get("correct"))
                                             for x in lines)
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()


if __name__ == "__main__":
    main()
