"""Differential conformance suite: adversarial forests × every registered
engine × float/quantized × serialization round trip.

Structure:

  * a catalog of deterministic **adversarial forests** — single-leaf
    trees, duplicate/constant thresholds, ±inf thresholds, unused
    features, 1-tree and 0-feature ensembles — each engine must agree
    with the naive traversal oracle on all of them;
  * quantized variants must be **bit-exact** across engines and **stay
    bit-exact under save/load** of both the packed IR and the compiled
    predictor artifact (the PR's acceptance invariant);
  * hypothesis strategies generate randomized adversarial forests on top
    (skipped cleanly when hypothesis isn't installed, as in the offline
    container — CI installs it).

Pallas engines run in interpret mode here (CPU): only the small
deterministic catalog includes them, the randomized sweeps stick to XLA.
"""
import numpy as np
import pytest

from repro import core, io
from repro.core import registry
from repro.trees.cart import Tree, TreeNode

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # container without hypothesis: CI covers it
    HAVE_HYPOTHESIS = False


# --------------------------------------------------------------------------- #
# Adversarial forest catalog
# --------------------------------------------------------------------------- #
def _leaf(*vals) -> TreeNode:
    return TreeNode(value=np.asarray(vals, dtype=np.float64))


def _split(f, t, left, right) -> TreeNode:
    return TreeNode(feature=f, threshold=t, left=left, right=right)


def _tree(root: TreeNode) -> Tree:
    def leaves(nd):
        return 1 if nd.is_leaf else leaves(nd.left) + leaves(nd.right)

    def depth(nd):
        return 0 if nd.is_leaf else 1 + max(depth(nd.left), depth(nd.right))

    return Tree(root, leaves(root), depth(root))


def _forest(roots, n_features, n_classes=1):
    return core.from_trees([_tree(r) for r in roots],
                           n_features=n_features, n_classes=n_classes)


def single_leaf_trees():
    """Every tree degenerate (no splits) — pure constants."""
    return _forest([_leaf(3.0), _leaf(-1.5), _leaf(0.25)], n_features=2)


def mixed_stump_and_deep():
    """Stumps padded against a deeper tree (ragged n_nodes)."""
    deep = _split(0, 0.0,
                  _split(1, -1.0, _leaf(1.0), _leaf(2.0)),
                  _split(1, 1.0, _leaf(3.0), _leaf(4.0)))
    return _forest([_leaf(10.0), deep, _leaf(-10.0)], n_features=2)


def duplicate_thresholds():
    """Every node the identical (feature, threshold) pair — RapidScorer's
    merge collapses the whole ensemble to one unique node."""
    def t():
        return _split(0, 0.7, _split(0, 0.7, _leaf(1.0), _leaf(2.0)),
                      _split(0, 0.7, _leaf(3.0), _leaf(4.0)))
    return _forest([t(), t(), t()], n_features=1)


def constant_threshold_chain():
    """A right-leaning chain reusing one threshold value on one feature."""
    chain = _split(0, 0.5, _leaf(1.0),
                   _split(0, 0.5, _leaf(2.0),
                          _split(0, 0.5, _leaf(3.0), _leaf(4.0))))
    return _forest([chain], n_features=3)       # + unused features


def inf_thresholds():
    """±inf thresholds: +inf sends everything left, -inf everything
    right (x <= -inf is false for finite x)."""
    t0 = _split(0, np.inf, _leaf(1.0), _leaf(99.0))
    t1 = _split(1, -np.inf, _leaf(99.0), _leaf(2.0))
    t2 = _split(0, 0.0, _split(1, np.inf, _leaf(3.0), _leaf(98.0)),
                _leaf(4.0))
    return _forest([t0, t1, t2], n_features=2)


def unused_features():
    """d=8 but only feature 5 is ever referenced."""
    t0 = _split(5, 0.1, _leaf(1.0), _leaf(2.0))
    t1 = _split(5, -0.3, _split(5, 0.8, _leaf(3.0), _leaf(4.0)),
                _leaf(5.0))
    return _forest([t0, t1], n_features=8)


def one_tree():
    return _forest([_split(0, 0.0, _leaf(-1.0), _leaf(1.0))], n_features=1)


def zero_features():
    """No features at all: every tree is a constant, X is (B, 0)."""
    return _forest([_leaf(2.0), _leaf(3.0)], n_features=0)


def multiclass_stumps():
    return _forest([_leaf(1.0, 0.0, 2.0), _leaf(0.5, 3.0, 0.0)],
                   n_features=2, n_classes=3)


ADVERSARIAL = {
    "single_leaf_trees": single_leaf_trees,
    "mixed_stump_and_deep": mixed_stump_and_deep,
    "duplicate_thresholds": duplicate_thresholds,
    "constant_threshold_chain": constant_threshold_chain,
    "inf_thresholds": inf_thresholds,
    "unused_features": unused_features,
    "one_tree": one_tree,
    "zero_features": zero_features,
    "multiclass_stumps": multiclass_stumps,
}
# quantization needs finite thresholds and at least one feature
QUANTIZABLE = sorted(set(ADVERSARIAL) - {"inf_thresholds", "zero_features"})

COMBOS = [(s.name, s.backend) for s in registry.specs()]
COMBO_IDS = [f"{n}/{b}" for n, b in COMBOS]
JAX_ENGINES = list(registry.engines("jax"))


def _X(forest, B=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1.5, size=(B, forest.n_features))
    if forest.n_features:
        # hit thresholds exactly: boundary rows are where engines diverge
        thr = forest.threshold[forest.feature >= 0]
        thr = thr[np.isfinite(thr.astype(np.float64))]
        for i, t in enumerate(thr[:B]):
            X[i, i % forest.n_features] = t
    return X


def _compile(forest, name, backend):
    return core.compile_forest(forest, engine=name, backend=backend)


# --------------------------------------------------------------------------- #
# float: every registered engine × every adversarial forest vs the oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_adversarial_float_agrees_with_oracle(case, name, backend):
    forest = ADVERSARIAL[case]()
    X = _X(forest)
    expect = forest.predict_oracle(X)
    got = _compile(forest, name, backend).predict(X)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6,
                               err_msg=f"{case}/{name}/{backend}")


# --------------------------------------------------------------------------- #
# quantized: engines bit-exact among themselves and vs the quantized oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_adversarial_quantized_engines_bitexact(case):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=1)
    qf = core.quantize_forest(forest, X)
    oracle = (qf.predict_oracle(core.quantize_inputs(qf, X))
              / core.leaf_scale(qf)).astype(np.float32)
    preds = {e: _compile(qf, e, "jax").predict(X) for e in JAX_ENGINES}
    for e, got in preds.items():
        np.testing.assert_array_equal(got, oracle,
                                      err_msg=f"{case}/{e}")


# --------------------------------------------------------------------------- #
# serialization round trips (the PR acceptance invariant)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_forest_roundtrip_is_lossless(case, tmp_path):
    forest = ADVERSARIAL[case]()
    p = str(tmp_path / "f.repro.npz")
    io.save_forest(forest, p)
    loaded = io.load_forest(p)
    for fld in ("feature", "threshold", "left", "right", "leaf_lo",
                "leaf_mid", "leaf_hi", "leaf_value", "n_nodes",
                "n_leaves_per_tree"):
        np.testing.assert_array_equal(getattr(forest, fld),
                                      getattr(loaded, fld), err_msg=fld)
    assert (loaded.n_trees, loaded.n_leaves, loaded.n_classes,
            loaded.n_features, loaded.max_depth) == \
           (forest.n_trees, forest.n_leaves, forest.n_classes,
            forest.n_features, forest.max_depth)
    X = _X(forest, B=8, seed=2)
    np.testing.assert_array_equal(forest.predict_oracle(X),
                                  loaded.predict_oracle(X))


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_quantized_predictor_roundtrip_bitexact(case, engine, tmp_path):
    """compile → save → load → predict is bit-identical to the in-memory
    prediction on quantized forests, for every registered XLA engine."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=10, seed=3)
    qf = core.quantize_forest(forest, X)
    pred = _compile(qf, engine, "jax")
    p = str(tmp_path / "pred.repro.npz")
    io.save_predictor(pred, p)
    loaded = io.load_predictor(p)
    np.testing.assert_array_equal(pred.predict(X), loaded.predict(X),
                                  err_msg=f"{case}/{engine}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
def test_float_predictor_roundtrip_within_tolerance(engine, tmp_path):
    forest = core.random_forest_ir(6, 16, 5, n_classes=2, seed=11,
                                   full=False)
    X = _X(forest, B=16, seed=4)
    pred = _compile(forest, engine, "jax")
    p = str(tmp_path / "pred.repro.npz")
    io.save_predictor(pred, p)
    loaded = io.load_predictor(p)
    np.testing.assert_allclose(pred.predict(X), loaded.predict(X),
                               rtol=0, atol=1e-6)


def test_quantized_forest_ir_roundtrip_preserves_quant_metadata(tmp_path):
    forest = duplicate_thresholds()
    X = _X(forest, B=32, seed=5)
    qf = core.quantize_forest(forest, X)
    p = str(tmp_path / "qf.repro.npz")
    io.save_forest(qf, p)
    loaded = io.load_forest(p)
    assert loaded.quant_scale == qf.quant_scale
    assert loaded.quant_bits == qf.quant_bits
    assert loaded.leaf_scale == qf.leaf_scale
    assert loaded.threshold.dtype == qf.threshold.dtype
    np.testing.assert_array_equal(loaded.feat_lo, qf.feat_lo)
    np.testing.assert_array_equal(loaded.feat_hi, qf.feat_hi)
    # and the compiled engines see identical inputs post-load
    np.testing.assert_array_equal(core.quantize_inputs(qf, X),
                                  core.quantize_inputs(loaded, X))


def test_import_compile_save_load_differential(tmp_path):
    """The full acceptance chain on an imported model: XGBoost dump →
    IR → quantize → compile (every XLA engine) → save → load → predict,
    loaded output bit-identical to in-memory, both matching the oracle."""
    from benchmarks.bench_coldstart import _forest_to_xgb_dump
    import json
    src = core.random_forest_ir(8, 16, 4, seed=21, full=False)
    dump_path = tmp_path / "model.json"
    dump_path.write_text(json.dumps(_forest_to_xgb_dump(src)))
    forest = io.load_model(str(dump_path))
    X = _X(forest, B=16, seed=6)
    np.testing.assert_allclose(forest.predict_oracle(X),
                               src.predict_oracle(X), rtol=1e-5, atol=1e-6)
    qf = core.quantize_forest(forest, X)
    oracle = (qf.predict_oracle(core.quantize_inputs(qf, X))
              / core.leaf_scale(qf)).astype(np.float32)
    for engine in JAX_ENGINES:
        pred = _compile(qf, engine, "jax")
        p = str(tmp_path / f"{engine}.repro.npz")
        io.save_predictor(pred, p)
        got = io.load_predictor(p).predict(X)
        np.testing.assert_array_equal(got, pred.predict(X), err_msg=engine)
        np.testing.assert_array_equal(got, oracle, err_msg=engine)


# --------------------------------------------------------------------------- #
# cascade conformance: a cascade whose gate never fires computes the same
# function as the underlying engine (docs/CASCADE.md)
# --------------------------------------------------------------------------- #
from repro.cascade import CascadePredictor, CascadeSpec, \
    FusedCascadePredictor, MarginGate, ScoreBoundGate

CASCADE_CASES = ["mixed_stump_and_deep", "multiclass_stumps",
                 "unused_features"]


def _mid_stages(forest):
    """A genuine 2-stage split when the forest allows one."""
    return (max(forest.n_trees // 2, 1), forest.n_trees)


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("case", CASCADE_CASES)
def test_cascade_single_stage_is_the_engine(case, name, backend):
    """One stage == the plain engine call: bit-exact for every registered
    engine/backend, float included (same program, same bits)."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=13)
    base = _compile(forest, name, backend)
    casc = CascadePredictor(forest, CascadeSpec((forest.n_trees,)),
                            engine=name, backend=backend)
    np.testing.assert_array_equal(casc.predict(X), base.predict(X),
                                  err_msg=f"{case}/{name}/{backend}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_cascade_gate_off_quantized_bitexact(case, engine):
    """Multi-stage, gate disabled (threshold=inf): integer stage sums
    under a pow2 leaf scale reassociate exactly — bit-exact with the
    base engine on quantized forests for every registered XLA engine."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=14)
    qf = core.quantize_forest(forest, X)
    base = _compile(qf, engine, "jax")
    casc = CascadePredictor(qf, CascadeSpec(_mid_stages(qf),
                                            MarginGate(np.inf)),
                            engine=engine)
    np.testing.assert_array_equal(casc.predict(X), base.predict(X),
                                  err_msg=f"{case}/{engine}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_cascade_gate_off_float_agrees(case, engine):
    """Float forests: stage-split reassociation moves the sum order, so
    the gate-off cascade matches within float tolerance (and matches the
    oracle like any engine)."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=15)
    base = _compile(forest, engine, "jax")
    casc = CascadePredictor(forest, CascadeSpec(_mid_stages(forest),
                                                MarginGate(np.inf)),
                            engine=engine)
    np.testing.assert_allclose(casc.predict(X), base.predict(X),
                               rtol=1e-5, atol=1e-6,
                               err_msg=f"{case}/{engine}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_cascade_roundtrip_bitexact(case, engine, tmp_path):
    """compile → save → load → predict is bit-identical for cascade
    artifacts on quantized forests, thresholds included."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=10, seed=16)
    qf = core.quantize_forest(forest, X)
    casc = CascadePredictor(qf, CascadeSpec(_mid_stages(qf),
                                            MarginGate(np.inf)),
                            engine=engine)
    p = str(tmp_path / "casc.repro.npz")
    io.save_predictor(casc, p)
    loaded = io.load_predictor(p)
    assert loaded.stages == casc.stages
    assert loaded.policy == casc.policy
    np.testing.assert_array_equal(casc.predict(X), loaded.predict(X),
                                  err_msg=f"{case}/{engine}")


# --------------------------------------------------------------------------- #
# fused vs staged: the one-jit execution (cascade/fused.py) must be
# indistinguishable from the host loop — scores bit-exact on quantized
# forests, identical class decisions, identical per-stage exit counts —
# for every registered engine/backend and across save/load
# --------------------------------------------------------------------------- #
# all-exit-at-stage-0 / mixed / never-exit: the three gate regimes hit
# the no-op early-termination branch, partial compaction, and the full
# every-stage path respectively
FIRING_THRESHOLDS = [0.0, 0.5, np.inf]


def _casc_pair(qf, name, backend, policy):
    staged = CascadePredictor(qf, CascadeSpec(_mid_stages(qf), policy),
                              engine=name, backend=backend)
    fused = FusedCascadePredictor(
        qf, CascadeSpec(_mid_stages(qf), policy, fused=True),
        engine=name, backend=backend)
    return staged, fused


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("case", CASCADE_CASES)
def test_fused_matches_staged_quantized_bitexact(case, name, backend):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=17)
    qf = core.quantize_forest(forest, X)
    for thr in FIRING_THRESHOLDS:
        staged, fused = _casc_pair(qf, name, backend, MarginGate(thr))
        tag = f"{case}/{name}/{backend}/margin{thr}"
        np.testing.assert_array_equal(fused.predict(X), staged.predict(X),
                                      err_msg=tag)
        np.testing.assert_array_equal(fused.last_exit_counts,
                                      staged.last_exit_counts, err_msg=tag)
        np.testing.assert_array_equal(fused.predict_class(X),
                                      staged.predict_class(X), err_msg=tag)


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("case", ["mixed_stump_and_deep",
                                  "multiclass_stumps"])
def test_fused_sound_gate_matches_staged_and_base(case, name, backend):
    """ScoreBoundGate exercises both decide paths (C=1 decision band,
    C>1 interval dominance); soundness means class decisions also equal
    the plain engine's."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=18)
    qf = core.quantize_forest(forest, X)
    staged, fused = _casc_pair(qf, name, backend, ScoreBoundGate())
    tag = f"{case}/{name}/{backend}"
    np.testing.assert_array_equal(fused.predict(X), staged.predict(X),
                                  err_msg=tag)
    np.testing.assert_array_equal(fused.last_exit_counts,
                                  staged.last_exit_counts, err_msg=tag)
    if forest.n_classes > 1:
        base = _compile(qf, name, backend)
        np.testing.assert_array_equal(fused.predict_class(X),
                                      base.predict_class(X), err_msg=tag)


def test_fused_exit_counts_nontrivial_and_engine_independent():
    """Guard against a vacuous equivalence: on this forest the gate
    splits the batch across stages (neither all-exit nor none), and the
    per-stage counts agree across every XLA engine and with staged."""
    forest = core.random_forest_ir(12, 16, 6, n_classes=3, seed=7,
                                   full=False)
    X = np.random.default_rng(20).normal(0, 2.0, size=(33, 6))
    qf = core.quantize_forest(forest, X)
    seen = set()
    for name in JAX_ENGINES:
        staged, fused = _casc_pair(qf, name, "jax", MarginGate(0.35))
        staged.predict(X)
        fused.predict(X)
        np.testing.assert_array_equal(fused.last_exit_counts,
                                      staged.last_exit_counts, err_msg=name)
        seen.add(tuple(fused.last_exit_counts))
    assert len(seen) == 1
    counts = next(iter(seen))
    assert 0 < counts[0] < 33, f"gate never/always fired: {counts}"


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", CASCADE_CASES)
def test_fused_roundtrip_bitexact(case, engine, tmp_path):
    """save → load restores a FusedCascadePredictor whose scores and
    exit counts are bit-identical to the in-memory fused predictor."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=10, seed=19)
    qf = core.quantize_forest(forest, X)
    fused = FusedCascadePredictor(
        qf, CascadeSpec(_mid_stages(qf), MarginGate(0.5), fused=True),
        engine=engine)
    p = str(tmp_path / "fused.repro.npz")
    io.save_predictor(fused, p)
    loaded = io.load_predictor(p)
    assert isinstance(loaded, FusedCascadePredictor) and loaded.fused
    assert loaded.spec.fused and loaded.stages == fused.stages
    np.testing.assert_array_equal(fused.predict(X), loaded.predict(X),
                                  err_msg=f"{case}/{engine}")
    np.testing.assert_array_equal(fused.last_exit_counts,
                                  loaded.last_exit_counts,
                                  err_msg=f"{case}/{engine}")


# --------------------------------------------------------------------------- #
# integer end-to-end (docs/QUANT.md): int-accum engines bit-exact vs the
# quantized oracle for every engine × backend (Pallas in interpret mode)
# and across save/load; FLInt engines reproduce the float engines'
# decisions exactly
# --------------------------------------------------------------------------- #
from repro.core.pipeline import CompilePlan, compile_plan
from repro.core.quantize import QuantSpec, accum_bits, flint_forest


def _q_oracle(qf, X):
    return (qf.predict_oracle(core.quantize_inputs(qf, X))
            / core.leaf_scale(qf)).astype(np.float32)


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_int_accum_bitexact_every_engine_backend(case, name, backend):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=23)
    qf = core.quantize_forest(forest, X, spec=QuantSpec(int_accum=True))
    assert qf.int_accum and qf.leaf_err_bound is not None
    got = _compile(qf, name, backend).predict(X)
    np.testing.assert_array_equal(got, _q_oracle(qf, X),
                                  err_msg=f"{case}/{name}/{backend}")


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
def test_int16_accumulation_bitexact(name, backend):
    """A tiny leaf scale keeps the worst-case sum inside int16 — the
    engines then accumulate in int16 (asserted via accum_bits) and must
    still match the oracle bit-for-bit."""
    forest = ADVERSARIAL["mixed_stump_and_deep"]()
    X = _X(forest, B=12, seed=24)
    qf = core.quantize_forest(forest, X,
                              spec=QuantSpec(scale=8.0, int_accum=True))
    assert accum_bits(qf) == 16
    got = _compile(qf, name, backend).predict(X)
    np.testing.assert_array_equal(got, _q_oracle(qf, X),
                                  err_msg=f"{name}/{backend}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_int_accum_predictor_roundtrip_bitexact(case, engine, tmp_path):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=10, seed=25)
    qf = core.quantize_forest(forest, X, spec=QuantSpec(int_accum=True))
    pred = _compile(qf, engine, "jax")
    p = str(tmp_path / "int.repro.npz")
    io.save_predictor(pred, p)
    loaded = io.load_predictor(p)
    np.testing.assert_array_equal(loaded.predict(X), _q_oracle(qf, X),
                                  err_msg=f"{case}/{engine}")


def test_int_accum_forest_roundtrip_preserves_metadata(tmp_path):
    forest = ADVERSARIAL["multiclass_stumps"]()
    X = _X(forest, B=16, seed=26)
    qf = core.quantize_forest(forest, X, spec=QuantSpec(int_accum=True))
    p = str(tmp_path / "qf.repro.npz")
    io.save_forest(qf, p)
    loaded = io.load_forest(p)
    assert loaded.int_accum and not loaded.flint
    assert loaded.leaf_err_bound == qf.leaf_err_bound
    np.testing.assert_array_equal(loaded.leaf_value, qf.leaf_value)


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_flint_reproduces_float_engine_exactly(case, engine):
    """FLInt rekeys f32 thresholds/inputs as monotone int32: traversal
    decisions — and therefore scores, which sum the identical f32 leaf
    table in the identical order — equal the float engine's bit-for-bit,
    ±inf thresholds included."""
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=27)
    ref = _compile(forest, engine, "jax").predict(X)
    pred = compile_plan(forest, CompilePlan(engine=engine, flint=True))
    np.testing.assert_array_equal(pred.predict(X), ref,
                                  err_msg=f"{case}/{engine}")


@pytest.mark.parametrize("engine", JAX_ENGINES)
def test_flint_predictor_roundtrip_bitexact(engine, tmp_path):
    forest = ADVERSARIAL["mixed_stump_and_deep"]()
    X = _X(forest, B=10, seed=28)
    pred = compile_plan(forest, CompilePlan(engine=engine, flint=True))
    p = str(tmp_path / "flint.repro.npz")
    io.save_predictor(pred, p)
    loaded = io.load_predictor(p)
    np.testing.assert_array_equal(loaded.predict(X), pred.predict(X),
                                  err_msg=engine)


def test_flint_forest_roundtrip_preserves_keys(tmp_path):
    forest = ADVERSARIAL["inf_thresholds"]()
    ff = flint_forest(forest)
    p = str(tmp_path / "ff.repro.npz")
    io.save_forest(ff, p)
    loaded = io.load_forest(p)
    assert loaded.flint and loaded.threshold.dtype == np.int32
    np.testing.assert_array_equal(loaded.threshold, ff.threshold)


def test_flint_rejected_on_pallas():
    forest = ADVERSARIAL["one_tree"]()
    with pytest.raises(ValueError, match="pallas"):
        compile_plan(forest, CompilePlan(engine="bitvector",
                                         backend="pallas", flint=True))


def test_flint_and_quant_mutually_exclusive():
    forest = ADVERSARIAL["one_tree"]()
    with pytest.raises(ValueError):
        compile_plan(forest, CompilePlan(engine="bitvector",
                                         quant=QuantSpec(), flint=True))


# --------------------------------------------------------------------------- #
# hypothesis: randomized adversarial forests (CI; skipped offline)
# --------------------------------------------------------------------------- #
if HAVE_HYPOTHESIS:
    import jax.numpy as jnp
    from repro.core.baselines import (compile_gemm, compile_native,
                                      eval_gemm, eval_native)
    from repro.core.quickscorer import (compile_qs, compile_qs_bitmm,
                                        eval_batch, eval_batch_bitmm)
    from repro.core.rapidscorer import compile_rs, eval_batch as rs_eval

    @st.composite
    def adversarial_forests(draw):
        """Random forests with adversarial structure mixed in: stumps
        alongside real trees, duplicated thresholds, unused features."""
        T = draw(st.integers(1, 4))
        L = draw(st.sampled_from([2, 4, 8, 16]))
        d_used = draw(st.integers(1, 4))
        d_extra = draw(st.integers(0, 3))          # unused feature tail
        seed = draw(st.integers(0, 10_000))
        full = draw(st.booleans())
        base = core.random_forest_ir(T, L, d_used, seed=seed, full=full)
        if draw(st.booleans()):                    # duplicate thresholds
            base.threshold = np.round(base.threshold, 1)
        n_stumps = draw(st.integers(0, 2))
        return base, d_used + d_extra, n_stumps, seed

    def _widen(base, d_total, n_stumps, seed):
        """Rebuild `base` + stumps as one ensemble over d_total features."""
        rng = np.random.default_rng(seed + 1)
        f = base
        if n_stumps == 0 and d_total == base.n_features:
            return f
        # reconstruct tree list from the IR arrays via oracle-equivalent
        # padding: easiest faithful widening is to bump n_features and
        # append stump trees directly at the Forest level
        import dataclasses
        stump_vals = rng.normal(size=(n_stumps, 1, 1))
        T, L = f.n_trees + n_stumps, f.n_leaves
        def pad(a, fill):
            out = np.full((n_stumps,) + a.shape[1:], fill, dtype=a.dtype)
            return np.concatenate([a, out])
        lv = np.zeros((n_stumps, L, f.n_classes), f.leaf_value.dtype)
        lv[:, 0, :] = stump_vals[:, 0, :]
        return dataclasses.replace(
            f, n_trees=T, n_features=d_total,
            feature=pad(f.feature, -1), threshold=pad(f.threshold, 0),
            left=pad(f.left, 0), right=pad(f.right, 0),
            leaf_lo=pad(f.leaf_lo, 0), leaf_mid=pad(f.leaf_mid, 0),
            leaf_hi=pad(f.leaf_hi, 0),
            leaf_value=np.concatenate([f.leaf_value, lv]),
            n_nodes=np.concatenate([f.n_nodes,
                                    np.zeros(n_stumps, np.int32)]),
            n_leaves_per_tree=np.concatenate(
                [f.n_leaves_per_tree, np.ones(n_stumps, np.int32)]))

    @settings(max_examples=20, deadline=None)
    @given(adversarial_forests(), st.integers(1, 24), st.integers(0, 9999))
    def test_hypothesis_engines_agree_with_oracle(af, B, xseed):
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(B, d_total))
        expect = forest.predict_oracle(X)
        Xj = jnp.asarray(X)
        got = {
            "qs": eval_batch(compile_qs(forest), Xj),
            "bitmm": eval_batch_bitmm(compile_qs_bitmm(forest), Xj),
            "rs": rs_eval(compile_rs(forest), Xj),
            "native": eval_native(compile_native(forest), Xj),
            "gemm": eval_gemm(compile_gemm(forest), Xj),
        }
        for e, y in got.items():
            np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4,
                                       atol=1e-5, err_msg=e)

    @st.composite
    def stage_splits(draw, max_trees=12):
        """Random cascade stage boundaries: 1..4 strictly increasing
        prefixes over a random tree count (the last may or may not cover
        the forest — normalize_stages must append/clamp either way)."""
        T = draw(st.integers(2, max_trees))
        ks = draw(st.lists(st.integers(1, T + 3), min_size=1, max_size=4,
                           unique=True))
        return T, tuple(sorted(ks))

    @settings(max_examples=20, deadline=None)
    @given(stage_splits(), st.integers(1, 16), st.integers(0, 9999))
    def test_hypothesis_cascade_gate_off_quantized_bitexact(split, B,
                                                            xseed):
        """Any stage split, gate disabled → bit-exact with the base
        engine on quantized forests; with the sound bound gate →
        predict_class exactly equal."""
        T, ks = split
        forest = core.random_forest_ir(T, 8, 4, n_classes=2,
                                       seed=xseed % 97, full=False)
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(B, 4))
        qf = core.quantize_forest(forest, X)
        base = core.compile_forest(qf, engine="bitvector")
        off = CascadePredictor(qf, CascadeSpec(ks, MarginGate(np.inf)))
        assert off.stages[-1] == T
        np.testing.assert_array_equal(off.predict(X), base.predict(X))
        sound = CascadePredictor(qf, CascadeSpec(ks, ScoreBoundGate()))
        np.testing.assert_array_equal(sound.predict_class(X),
                                      base.predict_class(X))

    @settings(max_examples=20, deadline=None)
    @given(adversarial_forests(), st.integers(1, 16), st.integers(0, 9999))
    def test_hypothesis_leaf_err_bound_never_exceeded(af, B, xseed):
        """The tracked worst-case bound is sound: under identical
        traversal (leaves-only quantization) the descaled integer score
        never drifts from the float score by more than
        ``leaf_err_bound``."""
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        ql = core.quantize_forest(
            forest, spec=QuantSpec(quantize_splits=False, int_accum=True))
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(B, d_total))
        got = (ql.predict_oracle(X) / core.leaf_scale(ql))
        expect = forest.predict_oracle(X)
        assert ql.leaf_err_bound is not None
        assert np.abs(got - expect).max() <= ql.leaf_err_bound + 1e-9

    @settings(max_examples=20, deadline=None)
    @given(adversarial_forests(), st.integers(1, 16), st.integers(0, 9999))
    def test_hypothesis_int_accum_cannot_overflow_and_is_bitexact(af, B,
                                                                  xseed):
        """``accum_bits`` is a compile-time proof: the structural
        worst-case |leaf sum| fits the chosen accumulator, so no input
        can overflow it; and the int-accum engines stay bit-exact vs the
        quantized oracle on randomized adversarial forests."""
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(B, d_total))
        qf = core.quantize_forest(forest, X, spec=QuantSpec(int_accum=True))
        bits = accum_bits(qf)
        worst = int(np.abs(qf.leaf_value.astype(np.int64))
                    .max(axis=(1, 2)).sum())
        assert worst <= np.iinfo(np.int16 if bits == 16 else np.int32).max
        oracle = _q_oracle(qf, X)
        Xq = jnp.asarray(core.quantize_inputs(qf, X))
        got = {
            "qs": eval_batch(compile_qs(qf), Xq),
            "bitmm": eval_batch_bitmm(compile_qs_bitmm(qf), Xq),
            "rs": rs_eval(compile_rs(qf), Xq),
            "native": eval_native(compile_native(qf), Xq),
            "gemm": eval_gemm(compile_gemm(qf), Xq),
        }
        for e, y in got.items():
            np.testing.assert_array_equal(np.asarray(y), oracle, err_msg=e)

    @settings(max_examples=20, deadline=None)
    @given(adversarial_forests(), st.integers(1, 16), st.integers(0, 9999))
    def test_hypothesis_flint_matches_float_engines(af, B, xseed):
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        X = np.random.default_rng(xseed).normal(
            0, 2.0, size=(B, d_total)).astype(np.float32)
        ff = flint_forest(forest)
        Xk = jnp.asarray(core.quantize_inputs(ff, X))
        Xf = jnp.asarray(X)
        np.testing.assert_array_equal(
            np.asarray(eval_batch(compile_qs(ff), Xk)),
            np.asarray(eval_batch(compile_qs(forest), Xf)))
        np.testing.assert_array_equal(
            np.asarray(eval_native(compile_native(ff), Xk)),
            np.asarray(eval_native(compile_native(forest), Xf)))

    @settings(max_examples=12, deadline=None)
    @given(adversarial_forests(), st.integers(0, 9999))
    def test_hypothesis_quantized_roundtrip_bitexact(af, xseed):
        # tmp_path is function-scoped (hypothesis health check forbids
        # it under @given); a context-managed tempdir cleans up per run
        import os
        import tempfile
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(8, d_total))
        qf = core.quantize_forest(forest, X)
        with tempfile.TemporaryDirectory() as tmp:
            p = os.path.join(tmp, "h.repro.npz")
            io.save_forest(qf, p)
            loaded = io.load_forest(p)
        Xq = core.quantize_inputs(qf, X)
        np.testing.assert_array_equal(core.quantize_inputs(loaded, X), Xq)
        np.testing.assert_array_equal(
            np.asarray(eval_batch(compile_qs(qf), jnp.asarray(Xq))),
            np.asarray(eval_batch(compile_qs(loaded), jnp.asarray(Xq))))
