"""The controls, at a size a test run holds: one precision below each
configuration's, read on the rows a run checks, fails the configuration's
limit, while the program itself passes it."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import control  # noqa: E402
import run  # noqa: E402
from test_bench_run import (CPU, SMALL, TRAFFIC,  # noqa: E402
                            bench_with_deferred)


@pytest.mark.parametrize("name", ["mnist_rf.online", "msn_gbt.batch"])
def test_control_fails_the_limit(name, tmp_path):
    bench = bench_with_deferred()
    cell, cfg, traffic = run.cell_spec(name, bench)
    cfg = dict(cfg, **SMALL[cfg["name"]], engines=["qs"])
    traffic = dict(traffic, **TRAFFIC[cell["traffic"]])
    m = run.measure_cell(name, 3, 1.0, False, bench=bench, cfg=cfg,
                         traffic=traffic, device=CPU,
                         cache_dir=str(tmp_path))
    r = control.readings(m)
    (key, limit), = cfg["limits"].items()
    assert r["checked"] > 0
    assert r["program"][key] <= limit
    assert r["control"][key] > limit
