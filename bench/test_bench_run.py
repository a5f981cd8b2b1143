"""Whole runs of small cells on the CPU, with the chip check skipped:
sound runs come out correct, and runs with the timed path broken
underneath come out not correct."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import run  # noqa: E402

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SMALL = {"mnist_rf": dict(n_trees=24, n_leaves=16, n_features=30,
                          calibration_rows=256),
         "msn_gbt": dict(n_trees=120, n_leaves=16, n_features=20)}
TRAFFIC = {"online": dict(rate_per_s=400, pool_rows=512, check_rows=300),
           "query": dict(rate_per_s=20, pool_rows=2048, check_rows=4000),
           "batch4096": dict(rows_per_call=256),
           "batch2048": dict(rows_per_call=128, check_rows=256)}


# The cells that wait outside BENCHMARK.json: the open-loop ones, for a
# knee measured on the chip with a generator that keeps up.
DEFERRED = {
    "workloads": [
        {"name": "mnist_rf.online", "config": "mnist_rf",
         "traffic": "online", "chips": 1},
        {"name": "msn_gbt.query", "config": "msn_gbt", "traffic": "query",
         "chips": 1}],
    "end_to_end": [
        {"name": n, "unit": "ms", "better": "lower", "source": "host_clock",
         "workloads": ["mnist_rf.online", "msn_gbt.query"]}
        for n in ("p50_ms", "p99_ms")],
}


def bench_with_deferred():
    """``BENCHMARK.json`` with the deferred cells added."""
    bench = run.load_json(ROOT, "BENCHMARK.json")
    return dict(bench, **{k: bench[k] + v for k, v in DEFERRED.items()})


def small_run(name, tmp_path, seed=7, engines=None, seconds=1.0, **tr):
    bench = bench_with_deferred()
    cell, cfg, traffic = run.cell_spec(name, bench)
    cfg = dict(cfg, **SMALL[cfg["name"]], engines=engines)
    traffic = {**traffic, **TRAFFIC[cell["traffic"]], **tr}
    return run.run_cell(name, seed, seconds, False, bench=bench, cfg=cfg,
                        traffic=traffic, device=CPU,
                        cache_dir=str(tmp_path))


@pytest.mark.parametrize("name", ["mnist_rf.online", "msn_gbt.query",
                                  "msn_gbt.batch", "mnist_rf.batch"])
def test_sound_run_is_correct(name, tmp_path):
    line = small_run(name, tmp_path)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["run"]["compiles_in_window"] == 0
    assert line["checks"]["checked"]["value"] > 0
    want = {"mnist_rf.online": {"p50_ms", "p99_ms", "setup_s"},
            "msn_gbt.query": {"p50_ms", "p99_ms", "setup_s"},
            "msn_gbt.batch": {"rows_per_s", "setup_s"},
            "mnist_rf.batch": {"rows_per_s", "setup_s"}}[name]
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())


def altered(orig):
    """One answer of every call, at a position the check cannot know,
    changed where it is produced by ten times the float limit."""
    where = np.random.default_rng(0)

    def predict_transformed(self, Xq):
        out = np.array(orig(self, Xq))
        out[where.integers(len(out))] += 0.5
        return out
    return predict_transformed


def half_left_out(orig):
    """Half of every batch left out, the mean of the rest in its place."""
    def predict_transformed(self, Xq):
        h = -(-len(Xq) // 2)
        out = np.array(orig(self, Xq[:h]))
        return np.concatenate(
            [out, np.repeat(out.mean(axis=0, keepdims=True),
                            len(Xq) - h, axis=0)])
    return predict_transformed


@pytest.mark.parametrize("fault", [altered, half_left_out])
@pytest.mark.parametrize("name", ["mnist_rf.online", "msn_gbt.query",
                                  "msn_gbt.batch", "mnist_rf.batch"])
def test_broken_timed_path_is_not_correct(name, fault, tmp_path,
                                          monkeypatch):
    from repro.core.registry import BasePredictor
    monkeypatch.setattr(BasePredictor, "predict_transformed",
                        fault(BasePredictor.predict_transformed))
    line = small_run(name, tmp_path, engines=["qs"])
    assert not line["correct"], line["checks"]


def last_position(orig):
    """The answer at one fixed position of every call changed: seen only
    where the window's checks cover every position of a call."""
    def predict_transformed(self, Xq):
        out = np.array(orig(self, Xq))
        out[-1] += 0.5
        return out
    return predict_transformed


@pytest.mark.parametrize("seed", [7, 8, 9])
@pytest.mark.parametrize("name", ["msn_gbt.batch", "mnist_rf.batch"])
def test_fault_at_one_position_is_not_correct(name, seed, tmp_path,
                                              monkeypatch):
    from repro.core.registry import BasePredictor
    monkeypatch.setattr(BasePredictor, "predict_transformed",
                        last_position(BasePredictor.predict_transformed))
    line = small_run(name, tmp_path, seed=seed, engines=["qs"],
                     seconds=3.0, check_rows=64)
    assert not line["correct"], line["checks"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mnist_rf.batch", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mnist_rf.batch", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
