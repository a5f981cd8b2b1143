"""Cascade subsystem: gate policies, calibration, the staged predictor,
pipeline/autotuner/server wiring, and packed-artifact round trips."""
import copy

import numpy as np
import pytest

from repro import core, io
from repro.cascade import (CascadePredictor, CascadeSpec,
                           FusedCascadePredictor, MarginGate, ProbaGate,
                           ScoreBoundGate, calibrate, normalize_stages,
                           policy_from_header, policy_to_header,
                           simulate_gate, tree_slice)
from repro.inference.server import ForestServer, ServerStats


@pytest.fixture(scope="module")
def qclass_forest():
    """Quantized multiclass forest — the cascade's home turf."""
    f = core.random_forest_ir(n_trees=24, n_leaves=16, n_features=8,
                              n_classes=3, seed=7, full=False)
    return core.quantize_forest(f, None)


def _X(forest, B=48, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1.2, size=(B, forest.n_features))


# --------------------------------------------------------------------------- #
# stage normalization + tree slicing
# --------------------------------------------------------------------------- #
def test_normalize_stages():
    assert normalize_stages((16, 48), 192) == (16, 48, 192)
    assert normalize_stages((48, 16, 16), 192) == (16, 48, 192)
    assert normalize_stages((500,), 192) == (192,)        # clamped
    assert normalize_stages((16, 500), 192) == (16, 192)
    assert normalize_stages((192,), 192) == (192,)
    with pytest.raises(ValueError, match="positive"):
        normalize_stages((0, 16), 192)


def test_tree_slice_matches_oracle(qclass_forest):
    X = _X(qclass_forest)
    Xq = core.quantize_inputs(qclass_forest, X)
    whole = qclass_forest.predict_oracle(Xq)
    parts = sum(tree_slice(qclass_forest, a, b).predict_oracle(Xq)
                for a, b in [(0, 8), (8, 20), (20, 24)])
    np.testing.assert_array_equal(whole, parts)
    sub = tree_slice(qclass_forest, 8, 20)
    assert sub.n_trees == 12
    assert sub.quant_scale == qclass_forest.quant_scale
    assert sub.leaf_scale == qclass_forest.leaf_scale


# --------------------------------------------------------------------------- #
# gate policies
# --------------------------------------------------------------------------- #
def test_margin_gate_inf_never_fires(qclass_forest):
    g = MarginGate(np.inf)
    g.prepare(qclass_forest, (8, 24))
    assert not g.exits(np.random.default_rng(0).normal(size=(10, 3)), 0).any()


def test_margin_gate_fires_on_confident_rows(qclass_forest):
    g = MarginGate(0.5)
    g.prepare(qclass_forest, (8, 24))
    scores = np.array([[10.0, 0.1, 0.1],     # confident → exit
                       [1.0, 1.0, 1.0]])     # uniform → stay
    ex = g.exits(scores, 0)
    assert ex.tolist() == [True, False]


def test_proba_gate(qclass_forest):
    g = ProbaGate(0.9)
    g.prepare(qclass_forest, (8, 24))
    scores = np.array([[10.0, 0.1, 0.1], [1.0, 1.0, 1.0]])
    assert g.exits(scores, 0).tolist() == [True, False]


def test_margin_gate_never_fires_on_regression(small_forest):
    """C=1: no margin exists, the heuristic gates must stay closed."""
    g = MarginGate(0.1)
    g.prepare(small_forest, (4, 8))
    assert not g.exits(np.ones((5, 1)), 0).any()


def test_score_bound_gate_is_sound(qclass_forest):
    """slack=0 bound gating never changes predict_class — for any data,
    by construction."""
    base = core.compile_forest(qclass_forest, engine="bitvector")
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 12, 24), ScoreBoundGate()))
    for seed in range(3):
        X = _X(qclass_forest, B=64, seed=seed)
        np.testing.assert_array_equal(casc.predict_class(X),
                                      base.predict_class(X))


def test_score_bound_gate_fires_when_provable():
    """A forest whose later trees have tiny leaves: early scores dominate
    the remaining bounds, so rows provably exit after stage 0."""
    f = core.random_forest_ir(n_trees=8, n_leaves=8, n_features=4,
                              n_classes=2, seed=3, full=False)
    f.leaf_value[4:] *= 1e-4            # trees 4..8 can barely move scores
    g = ScoreBoundGate()
    g.prepare(f, (4, 8))
    casc = CascadePredictor(f, CascadeSpec((4, 8), ScoreBoundGate()))
    casc.predict(_X(f, B=64, seed=1))
    assert casc.last_exit_counts[0] > 0            # some rows proved early
    base = core.compile_forest(f, engine="bitvector")
    X = _X(f, B=64, seed=1)
    np.testing.assert_array_equal(casc.predict_class(X),
                                  base.predict_class(X))


def test_score_bound_gate_c1_decision():
    """C=1 (GBM logit shape): exits only when the sign vs decision is
    provably fixed."""
    f = core.random_forest_ir(n_trees=6, n_leaves=8, n_features=4,
                              n_classes=1, seed=5, full=False)
    g = ScoreBoundGate()
    g.prepare(f, (3, 6))
    lo, hi = g._rest_min[0][0], g._rest_max[0][0]
    fixed_pos = np.array([[abs(lo) + 1.0]])        # score + lo > 0
    fixed_neg = np.array([[-(abs(hi) + 1.0)]])     # score + hi < 0
    undecided = np.array([[0.0]])
    assert g.exits(fixed_pos, 0).tolist() == [True]
    assert g.exits(fixed_neg, 0).tolist() == [True]
    assert g.exits(undecided, 0).tolist() == [False]


def test_policy_header_roundtrip():
    for pol in (MarginGate(0.85), ProbaGate(0.99), MarginGate(np.inf),
                ScoreBoundGate(slack=0.5, decision=1.0)):
        h = policy_to_header(pol)
        back = policy_from_header(h)
        assert type(back) is type(pol)
        assert back == pol
    with pytest.raises(ValueError, match="GatePolicy"):
        policy_from_header({"class": "repro.core.forest:Forest",
                            "config": {}})


def test_disabled_gate_header_is_strict_json():
    """MarginGate(inf) — calibrate's fallback — must serialize to
    RFC-8259 JSON: json.dumps would otherwise emit the nonstandard
    ``Infinity`` literal into the packed artifact header."""
    import json
    h = policy_to_header(MarginGate(np.inf))
    text = json.dumps(h, allow_nan=False)          # raises on Infinity
    back = policy_from_header(json.loads(text))
    assert back.threshold == np.inf


# --------------------------------------------------------------------------- #
# predictor: gating mechanics + exit accounting
# --------------------------------------------------------------------------- #
def test_exit_counts_sum_to_batch(qclass_forest):
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 12), MarginGate(0.3)))
    X = _X(qclass_forest, B=37)
    casc.predict(X)
    assert casc.last_exit_counts.sum() == 37
    casc.predict(X[:5])
    assert casc.last_exit_counts.sum() == 5
    assert casc.exit_counts.sum() == 42
    np.testing.assert_allclose(casc.exit_fractions.sum(), 1.0)
    assert (qclass_forest.n_trees >= casc.mean_trees_evaluated >= 6)


def test_gated_rows_carry_prefix_scores(qclass_forest):
    """A row that exits at stage k returns exactly the cumulative score
    of stages <= k (the gate simulation is the predictor's semantics)."""
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 12), MarginGate(0.3)))
    X = _X(qclass_forest, B=40, seed=2)
    got = casc.predict(X)
    cum = casc.cumulative_scores(X)
    pol = copy.copy(casc.policy)
    exit_stage, expect = simulate_gate(pol, cum)
    np.testing.assert_array_equal(got, expect)
    counts = np.bincount(exit_stage, minlength=len(casc.stages))
    np.testing.assert_array_equal(counts, casc.last_exit_counts)


def test_empty_batch(qclass_forest):
    casc = CascadePredictor(qclass_forest, CascadeSpec((6, 12)))
    out = casc.predict(np.zeros((0, qclass_forest.n_features)))
    assert out.shape == (0, 3)
    assert casc.last_exit_counts.sum() == 0


def test_predict_proba_matches_base_when_gate_off(qclass_forest):
    base = core.compile_forest(qclass_forest, engine="bitvector")
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((8, 24), MarginGate(np.inf)))
    X = _X(qclass_forest, B=16, seed=4)
    np.testing.assert_array_equal(casc.predict_proba(X),
                                  base.predict_proba(X))


def test_predictor_protocol(qclass_forest):
    from repro.core.registry import Predictor
    casc = CascadePredictor(qclass_forest, CascadeSpec((8, 24)))
    assert isinstance(casc, Predictor)
    assert casc.host_forest() is qclass_forest
    X = _X(qclass_forest, B=4)
    np.testing.assert_array_equal(
        casc.transform_inputs(X), core.quantize_inputs(qclass_forest, X))


def test_stage_recompiles_are_bucketed(qclass_forest, monkeypatch):
    """Shrinking batches must hit stage engines at power-of-two sizes:
    distinct raw batch sizes inside one bucket → one evaluated shape."""
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 24), MarginGate(np.inf)))
    seen = []
    stage0 = casc.stage_predictors[0]
    orig = stage0.predict_transformed

    def spy(X):
        seen.append(X.shape[0])
        return orig(X)

    monkeypatch.setattr(stage0, "predict_transformed", spy)
    for B in (3, 9, 15, 16):
        casc.predict(_X(qclass_forest, B=B))
    assert set(seen) == {4, 16}        # buckets, not raw sizes


def test_inputs_quantized_once_not_per_stage(qclass_forest, monkeypatch):
    """A K-stage cascade must transform each batch once — not once per
    stage — while producing identical scores."""
    from repro.core import quantize as qmod
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 12, 24), MarginGate(np.inf)))
    assert casc._pre_transform
    calls = []
    orig = qmod.quantize_inputs

    def spy(forest, X):
        calls.append(X.shape)
        return orig(forest, X)

    monkeypatch.setattr(qmod, "quantize_inputs", spy)
    # predictor module binds quantize_inputs at import; patch there too
    import repro.cascade.predictor as pmod
    monkeypatch.setattr(pmod, "quantize_inputs", spy)
    X = _X(qclass_forest, B=16, seed=21)
    got = casc.predict(X)
    assert len(calls) == 1
    base = core.compile_forest(qclass_forest, engine="bitvector")
    np.testing.assert_array_equal(got, base.predict(X))


def test_autotuned_cascade_winner_has_clean_exit_stats(class_forest,
                                                       monkeypatch):
    """The sweep's synthetic benchmark rows must not pollute the served
    exit accounting of a returned cascade predictor.  The cascade is
    forced to win by pinning the measured timings, so the polluted
    best-so-far predictor is exactly the one handed back."""
    from repro.core import engine_select
    engine_select.clear_cache()
    spec = CascadeSpec(stages=(2, 12), policy=MarginGate(0.0))
    cascade_name = f"qs@{spec.tag()}"

    real_bench = engine_select._bench_once

    def rigged(pred, X, repeats):
        real_bench(pred, X, repeats)       # benchmark rows really flow
        return 0.0 if isinstance(pred, CascadePredictor) else 1.0

    monkeypatch.setattr(engine_select, "_bench_once", rigged)
    c = engine_select.choose(class_forest, 16, engines=("qs",),
                             cascade_specs=(spec,), cache_path=None,
                             repeats=2)
    assert c.engine == cascade_name
    assert isinstance(c.predictor, CascadePredictor)
    assert c.predictor.exit_counts.sum() == 0
    engine_select.clear_cache()


# --------------------------------------------------------------------------- #
# satellite regression: survivor padding must be zero rows, not repeats
# of row 0 — and padding must never leak into gates or exit accounting
# --------------------------------------------------------------------------- #
def test_stage_padding_rows_are_zero_and_inert(qclass_forest, monkeypatch):
    casc = CascadePredictor(qclass_forest,
                            CascadeSpec((6, 24), MarginGate(0.3)))
    captured = []
    stage0 = casc.stage_predictors[0]
    orig = stage0.predict_transformed

    def spy(X):
        captured.append(np.asarray(X).copy())
        return orig(X)

    monkeypatch.setattr(stage0, "predict_transformed", spy)
    X = _X(qclass_forest, B=13, seed=30)
    X[0] = 50.0                  # pathological first row
    got = casc.predict(X)
    counts = casc.last_exit_counts.copy()
    assert captured[0].shape[0] == 16
    assert not np.any(captured[0][13:]), \
        "bucket padding must be zero rows, not row-0 repeats"
    # padding inertness: each row's score and exit stage are what the
    # row gets when predicted alone (any padding influence would shift
    # the gate statistics of some batch composition)
    casc.reset_exit_stats()
    rows, stages = [], []
    for i in range(13):
        rows.append(casc.predict(X[i:i + 1]))
        stages.append(int(np.flatnonzero(casc.last_exit_counts)[0]))
    np.testing.assert_array_equal(got, np.concatenate(rows))
    np.testing.assert_array_equal(
        np.bincount(stages, minlength=len(casc.stages)), counts)


# --------------------------------------------------------------------------- #
# fused execution: one jitted computation, same observable behavior
# --------------------------------------------------------------------------- #
def test_fused_spec_tag_keys_new_cache_entries(qclass_forest):
    staged_spec = CascadeSpec((6, 24), MarginGate(0.3))
    fused_spec = CascadeSpec((6, 24), MarginGate(0.3), fused=True)
    assert "cascade-fused=" in fused_spec.tag()
    assert fused_spec.tag() != staged_spec.tag()


def test_compile_forest_fused_plan_records(qclass_forest):
    pred = core.compile_forest(qclass_forest, engine="bitmm",
                               cascade=CascadeSpec((8, 24), fused=True))
    assert isinstance(pred, FusedCascadePredictor) and pred.fused
    assert "(fused)" in pred.plan.describe()
    assert "fused" in pred.describe()
    assert pred.host_syncs == 1


def test_staged_host_syncs_is_stage_count(qclass_forest):
    casc = CascadePredictor(qclass_forest, CascadeSpec((6, 12, 24)))
    assert casc.host_syncs == 3


def test_fused_matches_staged_across_batch_sizes(qclass_forest):
    staged = CascadePredictor(qclass_forest,
                              CascadeSpec((6, 12, 24), MarginGate(0.3)))
    fused = FusedCascadePredictor(
        qclass_forest, CascadeSpec((6, 12, 24), MarginGate(0.3),
                                   fused=True))
    for B in (1, 3, 37, 64):
        X = _X(qclass_forest, B=B, seed=B)
        np.testing.assert_array_equal(fused.predict(X), staged.predict(X),
                                      err_msg=f"B={B}")
        np.testing.assert_array_equal(fused.last_exit_counts,
                                      staged.last_exit_counts,
                                      err_msg=f"B={B}")
    assert fused.exit_counts.sum() == staged.exit_counts.sum() == 105


def test_fused_empty_batch(qclass_forest):
    fused = FusedCascadePredictor(qclass_forest,
                                  CascadeSpec((6, 12), fused=True))
    out = fused.predict(np.zeros((0, qclass_forest.n_features)))
    assert out.shape == (0, 3)
    assert fused.last_exit_counts.sum() == 0


def test_fused_set_policy_rebuilds_program(qclass_forest):
    """The fused trace closes over the gate — swapping the policy must
    swap the compiled behavior, not serve a stale jit."""
    fused = FusedCascadePredictor(
        qclass_forest, CascadeSpec((6, 12, 24), MarginGate(np.inf),
                                   fused=True))
    X = _X(qclass_forest, B=20, seed=31)
    fused.predict(X)
    assert fused.last_exit_counts.tolist() == [0, 0, 20]   # never exits
    fused.set_policy(MarginGate(0.0))
    fused.predict(X)
    assert fused.last_exit_counts.tolist() == [20, 0, 0]   # all exit at 0


def test_fused_server_reports_exit_fractions(qclass_forest):
    """The in-graph exit-count vector must feed ServerStats exactly like
    the staged loop's host-side accounting."""
    fused = core.compile_forest(qclass_forest, engine="bitvector",
                                cascade=CascadeSpec((6, 24),
                                                    MarginGate(0.3),
                                                    fused=True))
    srv = ForestServer(fused, max_batch=8, max_wait_ms=1.0)
    X = _X(qclass_forest, B=24, seed=12)
    for i in range(24):
        srv.submit(X[i], arrival_s=float(i) * 1e-4)
    srv.flush(now_s=1.0)
    s = srv.stats.summary()
    assert len(s["exit_fractions"]) == 2
    np.testing.assert_allclose(np.sum(s["exit_fractions"]), 1.0)
    assert sum(srv.stats.stage_exit_counts) == 24


def test_autotuner_accepts_fused_candidates(class_forest, monkeypatch):
    """A fused spec flows through engine_select.choose under its
    cascade-fused tag (key-missing pre-fusion cache entries)."""
    from repro.core import engine_select
    engine_select.clear_cache()
    spec = CascadeSpec(stages=(2, 12), policy=MarginGate(0.0), fused=True)
    assert "cascade-fused=" in spec.tag()

    real_bench = engine_select._bench_once

    def rigged(pred, X, repeats):
        real_bench(pred, X, repeats)
        return 0.0 if isinstance(pred, CascadePredictor) else 1.0

    monkeypatch.setattr(engine_select, "_bench_once", rigged)
    c = engine_select.choose(class_forest, 16, engines=("qs",),
                             cascade_specs=(spec,), cache_path=None,
                             repeats=2)
    assert c.engine == f"qs@{spec.tag()}"
    assert isinstance(c.predictor, FusedCascadePredictor)
    assert c.predictor.exit_counts.sum() == 0
    engine_select.clear_cache()


# --------------------------------------------------------------------------- #
# calibration
# --------------------------------------------------------------------------- #
def _trained_cascade(trained_rf, magic_ds, engine="bitvector"):
    qf = core.quantize_forest(core.from_random_forest(trained_rf),
                              magic_ds.X_train)
    casc = core.compile_forest(qf, engine=engine,
                               cascade=CascadeSpec((8, 32)))
    return qf, casc


def test_calibrate_respects_accuracy_floor(trained_rf, magic_ds):
    qf, casc = _trained_cascade(trained_rf, magic_ds)
    n = len(magic_ds.X_test) // 2
    res = calibrate(casc, magic_ds.X_test[:n], magic_ds.y_test[:n],
                    floor_pp=0.5)
    assert res.accuracy >= res.full_accuracy - 0.5 / 100
    assert res.mean_trees <= qf.n_trees
    # every reported candidate row is self-consistent
    for row in res.table:
        assert row["mean_trees"] <= qf.n_trees
        np.testing.assert_allclose(np.sum(row["exit_fractions"]), 1.0)
    # the winner actually installs and gates
    casc.set_policy(res.policy)
    casc.reset_exit_stats()
    acc = (casc.predict_class(magic_ds.X_test[n:])
           == magic_ds.y_test[n:]).mean()
    assert acc >= res.full_accuracy - 0.02     # held-out sanity, loose
    assert casc.exit_counts.sum() == len(magic_ds.X_test) - n


def test_calibrate_zero_floor_falls_back_to_exact(trained_rf, magic_ds):
    """floor_pp=0 admits only candidates with zero in-sample drop; the
    disabled-gate fallback guarantees one always exists."""
    _, casc = _trained_cascade(trained_rf, magic_ds)
    n = len(magic_ds.X_test) // 2
    res = calibrate(casc, magic_ds.X_test[:n], magic_ds.y_test[:n],
                    floor_pp=0.0, policies=[MarginGate(0.01)])
    assert res.accuracy >= res.full_accuracy


# --------------------------------------------------------------------------- #
# pipeline + compile_forest wiring
# --------------------------------------------------------------------------- #
def test_compile_forest_cascade_plan_records(qclass_forest):
    pred = core.compile_forest(qclass_forest, engine="bitmm",
                               cascade=CascadeSpec((8, 24)))
    assert isinstance(pred, CascadePredictor)
    names = [r.name for r in pred.plan.records]
    assert "cascade" in names and "lower" in names
    assert "stages=8/24" in pred.plan.describe()
    assert "cascade" in pred.plan.describe()


def test_cascade_rejects_multi_device(qclass_forest):
    with pytest.raises(ValueError, match="cascade"):
        core.compile_plan(qclass_forest, engine="bitvector",
                          n_devices=2, cascade=CascadeSpec((8, 24)))


# --------------------------------------------------------------------------- #
# packed artifacts
# --------------------------------------------------------------------------- #
def test_cascade_save_load_bitexact_with_thresholds(qclass_forest,
                                                    tmp_path):
    casc = core.compile_forest(qclass_forest, engine="bitvector",
                               cascade=CascadeSpec((6, 12, 24),
                                                   MarginGate(0.35)))
    X = _X(qclass_forest, B=32, seed=9)
    p = str(tmp_path / "casc.repro.npz")
    io.save_predictor(casc, p)
    assert io.peek(p)["kind"] == "cascade"
    loaded = io.load_predictor(p)
    assert isinstance(loaded, CascadePredictor)
    assert loaded.stages == casc.stages
    assert loaded.policy == casc.policy            # threshold round-trips
    np.testing.assert_array_equal(casc.predict(X), loaded.predict(X))
    np.testing.assert_array_equal(loaded.last_exit_counts,
                                  casc.last_exit_counts)
    assert "deserialize" in loaded.plan.describe()


def test_cascade_save_rejects_nonserializable_engine(qclass_forest,
                                                     tmp_path):
    casc = CascadePredictor(qclass_forest, CascadeSpec((8, 24)),
                            engine="bitvector", backend="pallas")
    with pytest.raises(ValueError, match="serial_arrays"):
        io.save_predictor(casc, str(tmp_path / "x.repro.npz"))


def test_forest_server_save_load_cascade(qclass_forest, tmp_path):
    casc = core.compile_forest(qclass_forest, engine="bitvector",
                               cascade=CascadeSpec((8, 24),
                                                   MarginGate(0.4)))
    srv = ForestServer(casc, max_batch=8, max_wait_ms=1.0)
    path = str(tmp_path / "server.repro.npz")
    srv.save(path)
    srv2 = ForestServer.load(path)
    assert isinstance(srv2.predictor, CascadePredictor)
    X = _X(qclass_forest, B=8, seed=11)
    np.testing.assert_array_equal(srv.predictor.predict(X),
                                  srv2.predictor.predict(X))
    assert srv2.batcher.max_batch == 8


# --------------------------------------------------------------------------- #
# serving: exit fractions in ServerStats
# --------------------------------------------------------------------------- #
def test_server_reports_exit_fractions(qclass_forest):
    casc = core.compile_forest(qclass_forest, engine="bitvector",
                               cascade=CascadeSpec((6, 24),
                                                   MarginGate(0.3)))
    srv = ForestServer(casc, max_batch=8, max_wait_ms=1.0)
    X = _X(qclass_forest, B=24, seed=12)
    for i in range(24):
        srv.submit(X[i], arrival_s=float(i) * 1e-4)
    srv.flush(now_s=1.0)
    s = srv.stats.summary()
    assert "exit_fractions" in s
    assert len(s["exit_fractions"]) == 2
    np.testing.assert_allclose(np.sum(s["exit_fractions"]), 1.0)
    assert sum(srv.stats.stage_exit_counts) == 24


def test_server_no_exit_fractions_for_plain_predictor(small_forest):
    pred = core.compile_forest(small_forest, engine="bitvector")
    srv = ForestServer(pred, max_batch=4, max_wait_ms=1.0)
    srv.submit(np.zeros(small_forest.n_features), arrival_s=0.0)
    srv.flush(now_s=1.0)
    assert "exit_fractions" not in srv.stats.summary()


# --------------------------------------------------------------------------- #
# satellite regression: idle ServerStats report null latencies, not 0.0
# --------------------------------------------------------------------------- #
def test_idle_server_stats_percentiles_are_null():
    s = ServerStats().summary()
    assert s["p50_ms"] is None and s["p99_ms"] is None
    assert s["n_requests"] == 0


def test_served_stats_percentiles_are_numbers(small_forest):
    pred = core.compile_forest(small_forest, engine="bitvector")
    srv = ForestServer(pred, max_batch=4, max_wait_ms=1.0)
    for i in range(4):
        srv.submit(np.zeros(small_forest.n_features),
                   arrival_s=float(i) * 1e-4)
    srv.flush(now_s=1.0)
    s = srv.stats.summary()
    assert isinstance(s["p50_ms"], float) and s["p50_ms"] > 0
    assert isinstance(s["p99_ms"], float) and s["p99_ms"] >= s["p50_ms"]
