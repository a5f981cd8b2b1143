"""Concurrent multi-tenant serving runtime (repro.inference.runtime):
threaded request loop, tenancy routing, SLO-aware adaptive batching,
shape warmup, manifest cold start — plus the bounded-stats and
monotonic-clock satellites in repro.inference.server."""
import threading
import time

import numpy as np
import pytest

from repro import core
from repro.inference import (AdaptiveBatchController, ForestServer,
                             Reservoir, ServingRuntime, SLOConfig)
from repro.inference.server import ServerStats


@pytest.fixture(scope="module")
def qpred_pair():
    """Two quantized forests + compiled predictors (distinct shapes so
    tenant routing mistakes can't alias)."""
    rng = np.random.default_rng(0)
    fa = core.random_forest_ir(n_trees=8, n_leaves=16, n_features=6,
                               n_classes=2, seed=0)
    fb = core.random_forest_ir(n_trees=12, n_leaves=16, n_features=6,
                               n_classes=3, seed=1)
    qa = core.quantize_forest(fa, rng.normal(size=(64, 6)))
    qb = core.quantize_forest(fb, rng.normal(size=(64, 6)))
    return (qa, core.compile_forest(qa, engine="bitvector"),
            qb, core.compile_forest(qb, engine="bitmm"))


# --------------------------------------------------------------------------- #
# Reservoir (bounded ServerStats satellite)
# --------------------------------------------------------------------------- #
def test_reservoir_exact_below_cap():
    r = Reservoir(cap=100)
    r.extend(float(i) for i in range(50))
    assert len(r) == 50 and r.n == 50
    assert list(r) == [float(i) for i in range(50)]
    assert r.mean() == pytest.approx(24.5)
    assert r.percentile(50) == pytest.approx(24.5)


def test_reservoir_bounded_memory_million_records():
    """A million-record run must not hold a million floats — retained
    storage is capped while count/sum stay exact."""
    r = Reservoir(cap=512)
    n = 1_000_000
    for i in range(n):
        r.append(1.0)
    assert r.n == n
    assert len(r) == 512                       # retained sample bounded
    assert len(r._sample) == 512               # the actual storage
    assert r.mean() == pytest.approx(1.0)
    assert r.percentile(99) == pytest.approx(1.0)


def test_reservoir_sample_is_plausible_and_deterministic():
    a, b = Reservoir(cap=64, seed=3), Reservoir(cap=64, seed=3)
    vals = list(np.linspace(0.0, 100.0, 10_000))
    a.extend(vals)
    b.extend(vals)
    assert list(a) == list(b)                  # seeded: deterministic
    # a uniform sample of a uniform ramp: median lands mid-range
    assert 20.0 < a.percentile(50) < 80.0


def test_reservoir_list_equality_and_empty():
    r = Reservoir()
    assert r == [] and not r
    assert ServerStats().batch_sizes == []
    r.append(2.0)
    assert r == [2.0] and bool(r)
    assert np.asarray(r).tolist() == [2.0]
    with pytest.raises(ValueError):
        Reservoir(cap=0)


def test_server_stats_summary_uses_exact_mean():
    st = ServerStats()
    st.n_batches = 0
    cap = st.batch_sizes.cap
    for i in range(cap + 100):                 # overflow the reservoir
        st.batch_sizes.append(4.0)
    assert st.summary()["mean_batch"] == pytest.approx(4.0)


# --------------------------------------------------------------------------- #
# Monotonic clock + block_until_ready satellites (ForestServer)
# --------------------------------------------------------------------------- #
def test_submit_default_clock_is_monotonic_not_wall(small_forest):
    pred = core.compile_forest(small_forest, engine="bitvector")
    srv = ForestServer(pred, max_batch=8, max_wait_ms=1.0)
    req = srv.submit(np.zeros(small_forest.n_features))
    # perf_counter timebase (process/boot origin), not the epoch wall
    # clock — an NTP step can no longer produce negative latencies
    assert abs(req.arrival_s - time.perf_counter()) < 5.0
    assert abs(req.arrival_s - time.time()) > 1e6


class _LazyScores:
    """Duck-typed 'device array still computing': block_until_ready
    sleeps, mimicking async dispatch that returned before finishing."""

    def __init__(self, arr, delay_s):
        self._arr = arr
        self.delay_s = delay_s
        self.blocked = False

    def block_until_ready(self):
        time.sleep(self.delay_s)
        self.blocked = True
        return self._arr

    def __iter__(self):
        return iter(self._arr)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._arr, dtype=dtype)


class _LazyPredictor:
    def __init__(self, delay_s=0.05, C=2):
        self.delay_s = delay_s
        self.C = C
        self.last = None

    def predict(self, X):
        self.last = _LazyScores(np.zeros((len(X), self.C)), self.delay_s)
        return self.last


def test_run_blocks_unfinished_scores_before_stamping_done(small_forest):
    """Regression (PR-6 class of bug): _run must block_until_ready the
    scores before stamping done_s, or async dispatch understates
    latency.  The lazy predictor 'finishes' 50 ms after predict()
    returns; the recorded latency must include that."""
    pred = _LazyPredictor(delay_s=0.05)
    srv = ForestServer(pred, max_batch=4, max_wait_ms=1.0)
    srv.submit(np.zeros(3), arrival_s=0.0)
    done = srv.flush(now_s=0.0)
    assert len(done) == 1
    assert pred.last.blocked                      # the sync happened
    assert done[0].latency_ms >= 50.0             # ...before done_s


# --------------------------------------------------------------------------- #
# Adaptive batching controller
# --------------------------------------------------------------------------- #
def test_controller_shrinks_on_violation_grows_on_headroom():
    slo = SLOConfig(target_p99_ms=10.0, window=8, min_batch=2,
                    max_batch=64, min_wait_ms=0.5, max_wait_ms=8.0)
    c = AdaptiveBatchController(slo, batch=64, wait_ms=8.0)
    for _ in range(8):
        c.observe(50.0)                            # way over budget
    assert c.decisions[-1]["action"] == "shrink"
    assert c.max_batch == 32 and c.max_wait_ms == 4.0
    for _ in range(5):                             # shrink to the floor
        for _ in range(8):
            c.observe(50.0)
    assert c.max_batch == 2 and c.max_wait_ms == 0.5   # clamped, bounded
    for _ in range(8):
        c.observe(1.0)                             # far under budget
    assert c.decisions[-1]["action"] == "grow"
    assert c.max_batch == 3 and c.max_wait_ms == pytest.approx(0.625)
    for _ in range(40):                            # grow to the ceiling
        for _ in range(8):
            c.observe(1.0)
    assert c.max_batch == 64 and c.max_wait_ms == 8.0  # clamped, bounded


def test_controller_holds_inside_band_and_is_deterministic():
    slo = SLOConfig(target_p99_ms=10.0, window=4, headroom=0.7,
                    max_batch=32, max_wait_ms=4.0)
    runs = []
    for _ in range(2):
        c = AdaptiveBatchController(slo, batch=16, wait_ms=2.0)
        trace = [8.0] * 4 + [20.0] * 4 + [1.0] * 4 + [9.0] * 4
        for v in trace:
            c.observe(v)
        runs.append([d["action"] for d in c.decisions])
    assert runs[0] == runs[1]                      # pure replay
    assert runs[0] == ["hold", "shrink", "grow", "hold"]


def test_controller_partial_window_no_decision_and_none_ignored():
    c = AdaptiveBatchController(SLOConfig(target_p99_ms=5.0, window=16),
                                batch=8, wait_ms=2.0)
    for _ in range(15):
        assert c.observe(3.0) is None
    assert c.observe(None) is None                 # incomplete latencies
    assert c.observe(3.0) is not None              # 16th closes the window


def test_controller_rejects_empty_bounds():
    with pytest.raises(ValueError, match="batch bounds"):
        AdaptiveBatchController(
            SLOConfig(target_p99_ms=5.0, min_batch=16, max_batch=8),
            batch=8, wait_ms=1.0)


def test_adaptive_runtime_virtual_clock_deterministic(qpred_pair):
    """The full pump path under a virtual clock: the controller's
    effective knobs change deterministically from observed (virtual)
    latencies, and stay within bounds."""
    qa, pa, *_ = qpred_pair
    slo = SLOConfig(target_p99_ms=0.5, window=4, min_batch=1,
                    max_batch=8, min_wait_ms=0.1, max_wait_ms=50.0)

    def run_once():
        rt = ServingRuntime(clock=lambda: 0.0)
        rt.add_model("m", pa, max_batch=8, max_wait_ms=50.0, slo=slo)
        X = np.zeros((32, qa.n_features))
        eff = []
        for i in range(32):
            # arrivals 10 ms apart; pump 60 ms later → every request
            # waits out the (virtual) deadline, so observed latency far
            # exceeds the 0.5 ms budget → the controller must shrink
            rt.submit("m", X[i], arrival_s=i * 0.01)
            rt.pump(now_s=i * 0.01 + 0.06)
            eff.append((rt.tenant("m").batcher.max_wait_ms,
                        rt.tenant("m").batcher.max_batch))
        rt.flush(now_s=10.0)
        return eff

    a, b = run_once(), run_once()
    assert a == b                                    # deterministic
    waits = [w for w, _ in a]
    assert waits[-1] < waits[0]                      # it shrank
    assert all(0.1 <= w <= 50.0 for w in waits)      # bounded
    assert all(1 <= mb <= 8 for _, mb in a)


# --------------------------------------------------------------------------- #
# Warmup
# --------------------------------------------------------------------------- #
def test_warmup_covers_ladder_and_freezes_trace_count(qpred_pair):
    """After warmup, serving any batch size adds zero new traces: the
    pad-to-bucket dispatch only ever presents warmed shapes."""
    qa, _, *_ = qpred_pair
    pred = core.compile_forest(qa, engine="bitvector")   # fresh jit cache
    rt = ServingRuntime()
    rt.add_model("m", pred, max_batch=13, max_wait_ms=1.0)
    warmed = rt.warmup()
    assert warmed == {"m": [1, 2, 4, 8, 16]}             # ladder to 2^ceil
    n_traces = pred._fn._cache_size()
    assert n_traces == 5
    X = np.random.default_rng(0).normal(size=(40, qa.n_features))
    for i in range(40):
        rt.submit("m", X[i], arrival_s=i * 1e-4)
        rt.pump(now_s=i * 1e-4)
    rt.flush(now_s=1.0)
    assert pred._fn._cache_size() == n_traces            # no cold shapes
    assert rt.summary("m")["n_requests"] == 40


def test_warmup_covers_folded_program_of_float32_rows(qpred_pair):
    """A Pallas predictor of a quantized forest runs float32 rows through
    a program of their own (folded thresholds); warmup traces it too, so
    float32 traffic adds no trace, and is served as the predictor
    scores it directly."""
    from repro.kernels.ops import pallas_qs_predictor
    qa, *_ = qpred_pair
    pred = pallas_qs_predictor(qa, block_b=32, block_t=4)
    rt = ServingRuntime()
    rt.add_model("m", pred, max_batch=8, max_wait_ms=1.0)
    rt.warmup()
    assert set(pred._programs) == {False, True}
    n_traces = pred.trace_cache_size()
    assert n_traces == 2                                 # one bucket each
    X = np.random.default_rng(1).normal(size=(12, qa.n_features)).astype(
        np.float32)
    reqs = []
    for i in range(12):
        reqs.append(rt.submit("m", X[i], arrival_s=i * 1e-4))
        rt.pump(now_s=i * 1e-4)
    rt.flush(now_s=1.0)
    assert pred.trace_cache_size() == n_traces           # no cold program
    np.testing.assert_array_equal(np.stack([r.result for r in reqs]),
                                  pred.predict(X))


def test_warmup_predictions_bit_identical(qpred_pair):
    qa, _, *_ = qpred_pair
    pred = core.compile_forest(qa, engine="rapidscorer")
    X = np.random.default_rng(1).normal(size=(9, qa.n_features))
    before = pred.predict(X)
    rt = ServingRuntime()
    rt.add_model("m", pred, max_batch=16)
    rt.warmup("m")
    np.testing.assert_array_equal(pred.predict(X), before)


def test_warmup_fused_cascade_resets_exit_stats(qpred_pair):
    from repro.cascade import CascadeSpec, MarginGate
    qa, *_ = qpred_pair
    fused = core.compile_forest(qa, engine="bitvector",
                                cascade=CascadeSpec(
                                    stages=(4, 8),
                                    policy=MarginGate(0.5), fused=True))
    rt = ServingRuntime()
    rt.add_model("casc", fused, max_batch=16)
    rt.warmup()
    # synthetic warmup rows must not pollute served exit accounting
    assert fused.exit_counts.sum() == 0
    n_traces = fused._jit_cache["prog"]._cache_size()
    assert n_traces >= 1
    X = np.random.default_rng(2).normal(size=(11, qa.n_features))
    for i in range(11):
        rt.submit("casc", X[i], arrival_s=i * 1e-4)
    rt.flush(now_s=1.0)
    # fused cascade buckets internally: the warmed shapes cover serving
    assert fused._jit_cache["prog"]._cache_size() == n_traces
    assert fused.exit_counts.sum() == 11


def test_warmup_respects_adaptive_upper_bound(qpred_pair):
    """Adaptive growth must never hit a cold shape: warmup pre-traces
    to the controller's max_batch bound, not the current effective."""
    qa, pa, *_ = qpred_pair
    rt = ServingRuntime()
    rt.add_model("m", pa, max_batch=4, max_wait_ms=1.0,
                 slo=SLOConfig(target_p99_ms=5.0, max_batch=32))
    assert rt.warmup() == {"m": [1, 2, 4, 8, 16, 32]}


# --------------------------------------------------------------------------- #
# Conformance: serving == synchronous predict, per engine
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ["bitvector", "bitmm", "native", "gemm"])
def test_served_scores_bit_identical_to_predict(qpred_pair, engine):
    """The padded/bucketed dispatch path must be bit-identical to the
    synchronous predictor.predict on quantized forests — including the
    odd batch tails that exercise the zero-padding."""
    qa, *_ = qpred_pair
    pred = core.compile_forest(qa, engine=engine)
    X = np.random.default_rng(3).normal(size=(23, qa.n_features))
    direct = pred.predict(X)
    rt = ServingRuntime()
    rt.add_model("m", pred, max_batch=5, max_wait_ms=1.0)   # odd batches
    reqs = [rt.submit("m", X[i], arrival_s=i * 1e-4) for i in range(23)]
    rt.flush(now_s=1.0)
    got = np.stack([r.result for r in reqs])
    np.testing.assert_array_equal(got, direct)


def test_served_cascade_exit_accounting_intact(qpred_pair):
    """Cascade tenants: scores match the synchronous path and the
    per-stage exit accounting reflects exactly the served rows."""
    from repro.cascade import CascadePredictor, CascadeSpec, MarginGate
    qa, *_ = qpred_pair
    spec = CascadeSpec(stages=(4, 8), policy=MarginGate(0.5))
    ref = CascadePredictor(qa, spec, engine="bitvector")
    served = CascadePredictor(qa, spec, engine="bitvector")
    X = np.random.default_rng(4).normal(size=(17, qa.n_features))
    direct = ref.predict(X)
    rt = ServingRuntime()
    rt.add_model("casc", served, max_batch=17, max_wait_ms=1.0)
    reqs = [rt.submit("casc", X[i], arrival_s=0.0) for i in range(17)]
    rt.flush(now_s=1.0)
    np.testing.assert_array_equal(np.stack([r.result for r in reqs]),
                                  direct)
    assert served.exit_counts.sum() == 17
    np.testing.assert_array_equal(served.exit_counts, ref.exit_counts)
    s = rt.summary("casc")
    assert "exit_fractions" in s and sum(s["exit_fractions"]) == \
        pytest.approx(1.0)


# --------------------------------------------------------------------------- #
# Concurrency: threaded loop, tenancy, shutdown
# --------------------------------------------------------------------------- #
def _hammer(rt, model_id, X, n_threads, per_thread):
    """n_threads × per_thread concurrent submissions; returns requests."""
    all_reqs, errs = [], []
    lock = threading.Lock()

    def worker(seed):
        rng = np.random.default_rng(seed)
        mine = []
        try:
            for _ in range(per_thread):
                i = int(rng.integers(0, len(X)))
                mine.append((i, rt.submit(model_id, X[i])))
        except Exception as e:                        # pragma: no cover
            errs.append(e)
        with lock:
            all_reqs.extend(mine)

    ts = [threading.Thread(target=worker, args=(s,))
          for s in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    return all_reqs


def test_threaded_exactly_once_single_tenant(qpred_pair):
    qa, pa, *_ = qpred_pair
    X = np.random.default_rng(5).normal(size=(32, qa.n_features))
    direct = pa.predict(X)
    rt = ServingRuntime()
    rt.add_model("m", pa, max_batch=16, max_wait_ms=0.5)
    with rt:
        reqs = _hammer(rt, "m", X, n_threads=8, per_thread=40)
        for _, r in reqs:
            r.wait(timeout=30)
    # exactly once: every request resolved, rids unique, totals add up
    assert len(reqs) == 320
    assert len({r.rid for _, r in reqs}) == 320
    for i, r in reqs:
        np.testing.assert_array_equal(r.result, direct[i])
        assert r.done_s is not None and r.latency_ms >= 0.0
    s = rt.summary("m")
    assert s["n_requests"] == 320
    assert rt.tenant("m").stats.batch_sizes.total == 320   # sum of sizes


def test_threaded_multi_tenant_routing(qpred_pair):
    qa, pa, qb, pb = qpred_pair
    X = np.random.default_rng(6).normal(size=(16, qa.n_features))
    da, db = pa.predict(X), pb.predict(X)
    assert da.shape[1] != db.shape[1]          # routing mistakes visible
    rt = ServingRuntime()
    rt.add_model("a", pa, max_batch=8, max_wait_ms=0.5)
    rt.add_model("b", pb, max_batch=8, max_wait_ms=0.5)
    rt.warmup()
    with rt:
        ra = _hammer(rt, "a", X, n_threads=4, per_thread=25)
        rb = _hammer(rt, "b", X, n_threads=4, per_thread=25)
        for _, r in ra + rb:
            r.wait(timeout=30)
    for i, r in ra:
        np.testing.assert_array_equal(r.result, da[i])
    for i, r in rb:
        np.testing.assert_array_equal(r.result, db[i])
    assert rt.summary("a")["n_requests"] == 100
    assert rt.summary("b")["n_requests"] == 100


def test_close_flushes_queued_requests_no_deadlock(qpred_pair):
    """Shutdown contract: whatever is still queued when close() is
    called completes exactly once; close joins within its timeout."""
    qa, pa, *_ = qpred_pair
    X = np.zeros((4, qa.n_features))
    rt = ServingRuntime()
    # deadline far away: requests sit in the queue until shutdown
    rt.add_model("m", pa, max_batch=64, max_wait_ms=60_000.0)
    rt.start()
    reqs = [rt.submit("m", X[i]) for i in range(4)]
    rt.close(timeout=30)
    for r in reqs:
        assert r.future.done()
        assert r.result is not None
    assert rt.summary("m")["n_requests"] == 4
    with pytest.raises(RuntimeError, match="closed"):
        rt.submit("m", X[0])
    rt.close()                                  # idempotent


def test_manual_close_flushes_without_thread(qpred_pair):
    qa, pa, *_ = qpred_pair
    rt = ServingRuntime(clock=lambda: 0.0)
    rt.add_model("m", pa, max_batch=64, max_wait_ms=60_000.0)
    r = rt.submit("m", np.zeros(qa.n_features))
    rt.close()
    assert r.future.done() and r.result is not None


def test_batch_exception_resolves_futures_and_worker_survives(qpred_pair):
    qa, pa, *_ = qpred_pair

    class Flaky:
        def __init__(self, inner):
            self.inner = inner
            self.fail_next = True

        def predict(self, X):
            if self.fail_next:
                self.fail_next = False
                raise RuntimeError("boom")
            return self.inner.predict(X)

        def host_forest(self):
            return self.inner.host_forest()

    rt = ServingRuntime()
    rt.add_model("m", Flaky(pa), max_batch=1, max_wait_ms=0.0)
    with rt:
        bad = rt.submit("m", np.zeros(qa.n_features))
        with pytest.raises(RuntimeError, match="boom"):
            bad.wait(timeout=30)
        good = rt.submit("m", np.zeros(qa.n_features))
        assert good.wait(timeout=30) is not None   # worker kept serving


def test_pump_and_flush_reject_while_threaded(qpred_pair):
    qa, pa, *_ = qpred_pair
    rt = ServingRuntime()
    rt.add_model("m", pa)
    with rt:
        with pytest.raises(RuntimeError, match="manual"):
            rt.pump()
        with pytest.raises(RuntimeError, match="manual"):
            rt.flush()


def test_unknown_tenant_and_duplicate_and_bad_id(qpred_pair):
    qa, pa, *_ = qpred_pair
    rt = ServingRuntime()
    rt.add_model("m", pa)
    with pytest.raises(ValueError, match="unknown model id"):
        rt.submit("nope", np.zeros(qa.n_features))
    with pytest.raises(ValueError, match="already serving"):
        rt.add_model("m", pa)
    with pytest.raises(ValueError, match="model id"):
        rt.add_model("bad/id", pa)


# --------------------------------------------------------------------------- #
# Manifest cold start
# --------------------------------------------------------------------------- #
def test_save_load_manifest_cold_start_bit_identical(qpred_pair, tmp_path):
    qa, _, qb, _ = qpred_pair
    rt = ServingRuntime()
    rt.add_model("alpha", core.compile_forest(qa, engine="bitvector"),
                 max_batch=16, max_wait_ms=3.0,
                 slo=SLOConfig(target_p99_ms=8.0, max_batch=64))
    rt.add_model("beta", core.compile_forest(qb, engine="bitmm"),
                 max_batch=8, max_wait_ms=1.5)
    X = np.random.default_rng(7).normal(size=(10, qa.n_features))
    da = rt.tenant("alpha").predictor.predict(X)
    db = rt.tenant("beta").predictor.predict(X)

    manifest = rt.save(tmp_path / "fleet")
    rt2 = ServingRuntime.load(manifest)
    assert set(rt2.model_ids) == {"alpha", "beta"}
    np.testing.assert_array_equal(rt2.tenant("alpha").predictor.predict(X),
                                  da)
    np.testing.assert_array_equal(rt2.tenant("beta").predictor.predict(X),
                                  db)
    # serving config + SLO round-trip
    ta, tb = rt2.tenant("alpha"), rt2.tenant("beta")
    assert ta.cfg_max_batch == 16 and ta.cfg_max_wait_ms == 3.0
    assert ta.controller is not None
    assert ta.controller.slo == SLOConfig(target_p99_ms=8.0, max_batch=64)
    assert tb.controller is None
    assert tb.cfg_max_batch == 8 and tb.cfg_max_wait_ms == 1.5
    # the loaded fleet actually serves, bit-identically
    reqs = [rt2.submit("alpha", X[i], arrival_s=0.0) for i in range(10)]
    rt2.flush(now_s=1.0)
    np.testing.assert_array_equal(np.stack([r.result for r in reqs]), da)
    # loading the directory (not the manifest file) works too
    rt3 = ServingRuntime.load(tmp_path / "fleet")
    assert set(rt3.model_ids) == {"alpha", "beta"}


def test_load_manifest_rejects_garbage(tmp_path):
    from repro.io import packed
    p = tmp_path / "manifest.json"
    p.write_text("not json {")
    with pytest.raises(ValueError, match="not a readable manifest"):
        packed.load_manifest(str(p))
    p.write_text('{"format": "something.else", "tenants": {}}')
    with pytest.raises(ValueError, match="unknown manifest format"):
        packed.load_manifest(str(p))
    p.write_text('{"format": "repro.tenants", "version": 99, '
                 '"tenants": {"m": {"artifact": "x.npz"}}}')
    with pytest.raises(ValueError, match="newer"):
        packed.load_manifest(str(p))
    p.write_text('{"format": "repro.tenants", "version": 1, '
                 '"tenants": {}}')
    with pytest.raises(ValueError, match="no tenants"):
        packed.load_manifest(str(p))
    with pytest.raises(ValueError, match="artifact"):
        packed.save_manifest(str(p), {"m": {"no_artifact": True}})


def test_from_forests_shares_autotune_cache(qpred_pair, tmp_path,
                                            monkeypatch):
    """N same-shaped tenants pay for ONE sweep: the second choose() is
    a cache hit (the runtime shares the process-wide autotune cache)."""
    from repro.core import engine_select
    qa, *_ = qpred_pair
    monkeypatch.setenv("REPRO_ENGINE_CACHE",
                       str(tmp_path / "cache.json"))
    engine_select.clear_cache()
    rt = ServingRuntime.from_forests(
        {"a": qa, "b": qa}, max_batch=8,
        engines=("qs", "native"), repeats=1)
    assert rt.tenant("a").engine_choice.from_cache is False
    assert rt.tenant("b").engine_choice.from_cache is True
    assert rt.tenant("a").engine_choice.engine == \
        rt.tenant("b").engine_choice.engine
    engine_select.clear_cache()
