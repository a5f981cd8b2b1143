"""Run one benchmark cell once, on the chip this process is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic are found by name: ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``, which names its driver in
``bench/drivers/``), and each per-layer metric's reader is
``bench/metrics/<metric>.py``.

One run: refuse a machine whose first JAX device is not a TPU (or that
has fewer chips than the cell asks for); build the configuration's
forest and the seed's traffic; build the predictor through the system's
own path (autotuner, serving runtime) and warm the cell's shapes; measure
for ``--seconds``; compare the served scores with the plain reference;
print one JSON line.  ``--trace 0`` reports the end-to-end metrics,
``--trace 1`` the per-layer ones, read from a profiler trace of the
window.  The numbers compared and their limits are the last lines on
standard error and the ``checks`` entry of the result line.

The process holds glibc's malloc in one mode (``steady_malloc``), so
that the host path's speed does not depend on where its allocator
happens to stand.  Compiled programs are cached in ``bench/.cache/jax``
(or where ``JAX_COMPILATION_CACHE_DIR`` says, if it is set) and the
autotuner's picks in ``bench/.cache/engines.json``, inside the
checkout, so only a checkout's first run of a cell compiles and sweeps.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402


def steady_malloc() -> None:
    """Hold glibc's malloc in one mode for the whole process: blocks up
    to 32 MiB come from the heap, and the heap is never trimmed.  Left to
    itself, glibc moves its mmap threshold as blocks are freed and trims
    the heap's top, so whether a call's large host temporaries (the
    quantized copy of a 2048 x 784 batch) reuse memory or are faulted in
    afresh each call depends on the order of earlier frees: a process at
    full speed or at about half, differently in each process."""
    import ctypes
    import ctypes.util
    try:
        mallopt = ctypes.CDLL(ctypes.util.find_library("c")).mallopt
    except (OSError, AttributeError, TypeError):
        return                                  # not glibc
    mallopt(-3, 32 << 20)                       # M_MMAP_THRESHOLD
    mallopt(-1, 2 ** 31 - 1)                    # M_TRIM_THRESHOLD


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str, bench: dict) -> tuple:
    """``(workload entry, configuration, traffic)`` of the cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = load_json(ROOT, next(c["file"] for c in bench["configs"]
                               if c["name"] == cell["config"]))
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    return cell, cfg, traffic


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def require_chip(chips: int) -> dict:
    """The device record; exits non-zero without a TPU or enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(count: int) -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:count]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def checks(model, out, ref: np.ndarray | None = None) -> dict:
    """``{name: {"value", "limit"}}`` of every number compared; ``ref``
    is the reference's scores of ``out.rows`` when already computed."""
    if ref is None:
        ref = model.reference(out.rows)
    want = ref[out.row_ids] if len(out.row_ids) \
        else np.zeros((0, model.cfg["n_classes"]))
    nums = model.numbers(out.got, want)
    nums["missing"] = float(out.missing)
    nums["checked"] = float(len(out.row_ids))
    limits = dict(model.cfg["limits"], missing=0)
    res = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()
           if k in limits}
    res["checked"] = {"value": nums["checked"], "limit": 1.0,
                      "at_least": True}
    return res


def passed(res: dict) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in res.values())


def setup_jax(cache_dir: str) -> None:
    """Persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, if set, else under ``cache_dir``; every program cached."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(cache_dir, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclass
class Measured:
    """One run up to the close of its window."""
    bench: dict
    ctx: object
    model: object
    out: object
    setup_s: float
    device: dict


def measure_cell(name: str, seed: int, seconds: float, tracing: bool, *,
                 bench: dict | None = None, cfg: dict | None = None,
                 traffic: dict | None = None, device: dict | None = None,
                 cache_dir: str = CACHE, t_start: float = T_START
                 ) -> Measured:
    """Set up cell ``name`` and measure one window.  ``bench``/``cfg``/
    ``traffic`` default to the checkout's files, and ``device`` to the
    chip check.  The program's state is freed before this returns."""
    import jax

    import cellmodel
    from harness import Ctx

    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell, cfg0, traffic0 = cell_spec(name, bench)
    cfg, traffic = cfg or cfg0, traffic or traffic0
    device = device or require_chip(cell["chips"])
    setup_jax(cache_dir)
    model = cellmodel.build(cfg)
    driver = load_module("drivers", traffic["driver"])
    ctx = Ctx(name, model, traffic, seed, seconds, tracing, cache_dir,
              device_kind=device["kind"])
    jax.monitoring.register_event_duration_secs_listener(ctx.on_event)
    try:
        session = driver.setup(ctx)
        setup_s = time.perf_counter() - t_start
        ctx.setup = session.setup
        out = driver.measure(session)
    finally:
        jax.monitoring.unregister_event_duration_listener(ctx.on_event)
    device = dict(device, memory_peak_bytes=memory_peak(device["count"]))
    del session
    gc.collect()
    return Measured(bench, ctx, model, out, setup_s, device)


def report(m: Measured) -> dict:
    """The result line of a measured run, with the check against the
    reference made now."""
    ctx, out, name = m.ctx, m.out, m.ctx.name
    res = checks(m.model, out)
    run = Run(ctx, out, m.model, m.setup_s)
    device = dict(m.device)
    if ctx.tracing:
        metrics = {}
        for metric in m.bench["per_layer"]:
            if applies(metric, name):
                v = load_module("metrics", metric["name"]).read(run)
                if v is not None:
                    metrics[metric["name"]] = {"value": v,
                                               "unit": metric["unit"]}
        device.update(busy_s=ctx.trace["busy_s"],
                      window_s=ctx.trace["window_s"])
    else:
        vals = dict(out.e2e, setup_s=m.setup_s)
        metrics = {e["name"]: {"value": vals[e["name"]], "unit": e["unit"]}
                   for e in m.bench["end_to_end"] if applies(e, name)}
    line = {"correct": passed(res) and out.failed == 0,
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if ctx.tracing:
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                             "idle_gaps": ctx.trace["idle_gaps"]}
    line["run"] = dict(out.notes, engine=run.setup.engine,
                       autotune_s=run.setup.autotune_s,
                       warmup_s=run.setup.warmup_s, **out.counters)
    line["checks"] = res
    return line


def run_cell(name: str, seed: int, seconds: float, tracing: bool,
             **kw) -> dict:
    """One run of cell ``name``; returns the result line as a dict
    (``measure_cell``'s keywords)."""
    return report(measure_cell(name, seed, seconds, tracing, **kw))


class Run:
    """What a per-layer metric's reader reads."""

    def __init__(self, ctx, out, model, setup_s: float):
        self.ctx, self.out, self.model, self.setup_s = ctx, out, model, setup_s
        self.setup = ctx.setup
        self.trace = ctx.trace

    def least_time(self, rows: int) -> float:
        """Seconds of the least time of a step over ``rows`` rows, at
        the peaks of the chip this run is on."""
        import roofline
        return roofline.least_time(self.model.work, rows,
                                   roofline.peaks(self.ctx.device_kind))[0]

    def step_wall_s(self) -> float:
        """Σ wall time of the window's steps, seconds."""
        return sum(w for _, w in self.out.steps)

    def least_total(self) -> float:
        """Σ least time over the window's steps, seconds."""
        return sum(self.least_time(r) for r, _ in self.out.steps)

    def host_ms(self) -> float | None:
        """Mean host time per step, ms: step wall time less the device's
        busy time, per step."""
        if self.trace is None or not self.out.steps:
            return None
        return (self.step_wall_s() - self.trace["busy_s"]) \
            / len(self.out.steps) * 1e3

    def idle_share(self) -> float | None:
        """Share, %, of the traced window with no operation on the
        device."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])

    def kernel_roofline(self) -> float | None:
        """Σ least time over the device's busy time, %."""
        if self.trace is None or not self.out.steps:
            return None
        return 100.0 * self.least_total() / self.trace["busy_s"]

    def step_mfu(self) -> float | None:
        """Σ least time over Σ step wall time, %."""
        if not self.out.steps:
            return None
        return 100.0 * self.least_total() / self.step_wall_s()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    steady_malloc()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit(f"no system under test at {ROOT}/src/repro")
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps({k: line[k] for k in ("run",)}), flush=True)
    for k, c in line["checks"].items():
        op = ">=" if c.get("at_least") else "<="
        print(f"check {k} {c['value']!r} {op} {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    line.pop("run")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
