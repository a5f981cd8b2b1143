"""The program's profiler spans (``repro.obs.trace.phase``): the
predictor's host path and the serving worker write ``repro.*`` spans into
a profiler trace, in call order and nested as documented, and feed the
per-request phases when observability is on."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import core
from repro.inference import ServingRuntime
from repro.inference.server import ForestServer
from repro.kernels.ops import pallas_qs_predictor
from repro.obs import (PREDICTOR_PHASES, MetricsRegistry, ServingMetrics,
                       collect, phase)
from repro.obs import trace as obs_trace

PREDICTOR_SPANS = ["repro." + p.removesuffix("_ms") for p in PREDICTOR_PHASES]


def _forest(seed=0, d=7, quantized=True):
    f = core.random_forest_ir(n_trees=6, n_leaves=8, n_features=d,
                              n_classes=3, seed=seed)
    if quantized:
        X = np.random.default_rng(seed).normal(size=(256, d))
        f = core.quantize_forest(f, X)
    return f


def _traced(tmp_path, fn):
    """``fn()`` under the profiler; every host event as ``(start_ns,
    end_ns, name, {arg: value})``, ordered by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                           for e in line.events)
    return sorted(out)


def _named(events, prefix):
    return [e for e in events if e[2].startswith(prefix)]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _assert_direct_children(children, parent, names):
    """``children`` are ``names`` in order, one after another, each
    inside ``parent`` and none inside another."""
    assert [e[2] for e in children] == names
    for a, b in zip(children, children[1:]):
        assert a[1] <= b[0]
    assert all(_inside(c, parent) for c in children)


@pytest.mark.parametrize("build", [
    lambda f: core.compile_forest(f, engine="bitvector"),
    lambda f: pallas_qs_predictor(f, block_b=32, block_t=4),
], ids=["xla-bitvector", "pallas-qs"])
def test_predict_writes_six_spans_in_order(build, tmp_path):
    pred = build(_forest())
    X = np.random.default_rng(1).normal(size=(20, 7))
    want = pred.predict(X)                       # compile outside the trace

    def call():
        with TraceAnnotation("test.call"):
            got = pred.predict(X)
        np.testing.assert_array_equal(got, want)

    events = _traced(tmp_path, call)
    caller, = _named(events, "test.call")
    spans = _named(events, "repro.")
    _assert_direct_children(spans, caller, PREDICTOR_SPANS)
    args = {name: a for _, _, name, a in spans}
    bucket = 32 if hasattr(pred, "block_b") else 20
    assert args["repro.quantize"] == {"rows": 20, "folded": 0}   # float64
    assert args["repro.tile_pad"] == {"rows": 20, "bucket": bucket}
    itemsize = 4 if hasattr(pred, "block_b") \
        else pred.transform_inputs(X).itemsize
    assert args["repro.h2d"] == {"bytes": bucket * 7 * itemsize}
    assert args["repro.d2h"]["bytes"] == bucket * 3 * 4
    assert "repro.launch" in args and "repro.wait" in args


@pytest.mark.parametrize("dtype,folded", [("float32", 1), ("float64", 0)])
def test_quantize_span_says_whether_rows_folded(dtype, folded, tmp_path):
    """A Pallas predictor of a quantized forest folds float32 rows into
    its thresholds and quantizes float64 rows on the host; the
    ``repro.quantize`` span's ``folded`` says which (a bool, read back
    as 0 or 1)."""
    pred = pallas_qs_predictor(_forest(), block_b=32, block_t=4)
    X = np.random.default_rng(4).normal(size=(20, 7)).astype(dtype)
    want = pred.predict(X)                       # compile outside the trace
    events = _traced(tmp_path, lambda: np.testing.assert_array_equal(
        pred.predict(X), want))
    (_, _, _, args), = _named(events, "repro.quantize")
    assert args == {"rows": 20, "folded": folded}


def test_predict_transformed_skips_quantize(tmp_path):
    pred = core.compile_forest(_forest(), engine="bitvector")
    Xq = pred.transform_inputs(np.random.default_rng(2).normal(size=(5, 7)))
    pred.predict_transformed(Xq)
    events = _traced(tmp_path, lambda: pred.predict_transformed(Xq))
    assert [e[2] for e in _named(events, "repro.")] == PREDICTOR_SPANS[1:]


def test_runtime_batch_span_and_sub_phases(tmp_path):
    reg = MetricsRegistry()
    rt = ServingRuntime(obs=reg)
    rt.add_model("m", core.compile_forest(_forest(3), engine="bitvector"),
                 max_batch=8, max_wait_ms=1.0)
    rt.warmup()
    X = np.random.default_rng(3).normal(size=(5, 7))

    def serve():
        for i in range(5):
            rt.submit("m", X[i], arrival_s=0.001 * i)
        return rt.flush(now_s=1.0)

    events = _traced(tmp_path, serve)
    batch, = _named(events, "repro.batch")
    assert batch[3] == {"tenant": "m", "n": 5, "bucket": 8}
    _assert_direct_children(
        [e for e in _named(events, "repro.")
         if e is not batch and _inside(e, batch)],
        batch, ["repro.form", "repro.pad"] + PREDICTOR_SPANS)

    spans = rt.obs.traces.recent()
    assert len(spans) == 5
    for s in spans:
        ph = s["phases"]
        assert set(PREDICTOR_PHASES) <= set(ph)
        assert sum(ph[p] for p in PREDICTOR_PHASES) <= ph["compute_ms"]
        assert {"queue_ms", "form_ms", "pad_ms", "sync_ms"} <= set(ph)
    snap = reg.snapshot()["repro_phase_ms"]["samples"]
    counts = {s["labels"]["phase"]: s["count"] for s in snap
              if s["labels"]["tenant"] == "m"}
    for p in PREDICTOR_PHASES + ("form_ms", "pad_ms", "compute_ms",
                                 "sync_ms"):
        assert counts[p] == 1, p
    assert counts["queue_ms"] == 5
    rt.close()


def test_runtime_without_obs_keeps_compute_and_sync():
    rt = ServingRuntime(obs=False)
    rt.add_model("m", core.compile_forest(_forest(4), engine="bitvector"),
                 max_batch=4, max_wait_ms=1.0)
    rt.warmup()
    reqs = [rt.submit("m", np.zeros(7), arrival_s=0.0) for _ in range(3)]
    rt.flush(now_s=1.0)
    assert all(r.span is None for r in reqs)
    summary = rt.tenant("m").stats.summary()
    assert summary["compute_p50_ms"] > 0 and summary["sync_p50_ms"] >= 0
    rt.close()


def test_forest_server_records_sub_phases():
    reg = MetricsRegistry()
    srv = ForestServer(core.compile_forest(_forest(5), engine="bitvector"),
                       max_batch=4, obs=ServingMetrics(reg), obs_label="f")
    for i in range(4):
        srv.submit(np.full(7, 0.1 * i), arrival_s=0.0)
    assert len(srv.flush(now_s=0.01)) == 4
    snap = reg.snapshot()["repro_phase_ms"]["samples"]
    got = {s["labels"]["phase"]: s for s in snap}
    for p in PREDICTOR_PHASES + ("form_ms", "compute_ms", "sync_ms"):
        assert got[p]["count"] == 1, p
    assert sum(got[p]["sum"] for p in PREDICTOR_PHASES) \
        <= got["compute_ms"]["sum"]


def test_phase_without_collector_writes_no_dict():
    assert getattr(obs_trace._local, "acc", None) is None
    span = phase("h2d", bytes=8)
    assert type(span) is TraceAnnotation      # the bare TraceMe, no timer
    with span:
        pass
    assert getattr(obs_trace._local, "acc", None) is None


def test_collect_sums_per_name_and_restores_outer():
    with collect() as outer:
        with phase("form"):
            pass
        with collect() as inner:
            for _ in range(3):
                with phase("h2d"):
                    pass
        with phase("form"):
            pass
    assert set(outer) == {"form_ms"} and outer["form_ms"] >= 0
    assert set(inner) == {"h2d_ms"} and inner["h2d_ms"] >= 0
    assert getattr(obs_trace._local, "acc", None) is None


def test_phase_collects_on_its_own_thread_only():
    import threading
    seen = {}

    def other():
        seen["acc"] = getattr(obs_trace._local, "acc", None)
        with phase("form"):
            pass

    with collect() as acc:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["acc"] is None and acc == {}


def test_phase_records_time_when_the_body_raises():
    with collect() as acc:
        with pytest.raises(ValueError):
            with phase("wait"):
                raise ValueError("boom")
    assert "wait_ms" in acc


# --------------------------------------------------------------------------- #
# the shape a predictor runs at: repro.launch's arguments and the
# repro_tree_rows_total counter
# --------------------------------------------------------------------------- #
WIDE = dict(n_trees=6, n_leaves=40, n_features=7)    # 2 mask words


def _wide_forest():
    return core.random_forest_ir(**WIDE, n_classes=2, seed=6, full=False)


@pytest.mark.parametrize("name,backend,tile", [
    ("bitvector", "jax", {}),
    # block_t=4 trees a tile, 40 node slots (39 nodes + bias, to 8) and
    # 40 leaf rows a tree, 2 tree tiles
    ("bitvector", "pallas", {"node_slots": 160, "leaf_rows": 160,
                             "tree_tiles": 2}),
    ("bitmm", "pallas", {"node_slots": 160, "leaf_rows": 160,
                         "tree_tiles": 2}),
    ("gemm", "pallas", {"node_slots": 160, "leaf_rows": 160,
                        "tree_tiles": 2}),
], ids=["xla-qs", "pallas-qs", "pallas-bitmm", "pallas-gemm"])
def test_launch_span_carries_the_tile_shape(name, backend, tile, tmp_path):
    from repro.core import registry
    kw = {"block_b": 32, "block_t": 4} if backend == "pallas" else {}
    pred = registry.build(_wide_forest(), name, backend, **kw)
    X = np.random.default_rng(7).normal(size=(20, 7))
    pred.predict(X)                              # compile outside the trace
    events = _traced(tmp_path, lambda: pred.predict(X))
    (_, _, _, args), = _named(events, "repro.launch")
    want = dict(engine=registry.get(name, backend).tune_name, trees=6,
                words=2, **tile)
    assert args == want
    assert pred.launch_args == want


def test_tree_rows_counter_counts_rows_times_trees():
    from repro.core import registry
    from repro.obs.metrics import set_default_registry
    pred = registry.build(_wide_forest(), "bitvector", "pallas",
                          block_b=32, block_t=4)
    X = np.random.default_rng(8).normal(size=(20, 7))
    mine = MetricsRegistry()
    old = set_default_registry(mine)
    try:
        pred.predict(X)
        pred.predict(X[:3])
    finally:
        set_default_registry(old)
    (sample,) = mine.snapshot()["repro_tree_rows_total"]["samples"]
    assert sample["labels"] == {"engine": "pallas-qs"}
    assert sample["value"] == (20 + 3) * WIDE["n_trees"]     # real rows


def test_tree_rows_counter_silent_when_obs_off():
    from repro.obs.metrics import set_default_registry
    pred = core.compile_forest(_wide_forest(), engine="bitvector")
    off = MetricsRegistry(enabled=False)
    old = set_default_registry(off)
    try:
        pred.predict(np.zeros((4, 7)))
    finally:
        set_default_registry(old)
    assert "repro_tree_rows_total" not in off.snapshot()
