"""Optimizer middle-end (repro.optim): pass semantics, the equivalence
contract, pipeline/autotuner/serialization/cascade wiring.

Structure:

  * unit tests per pass on hand-built forests where the expected rewrite
    is known exactly;
  * the conformance sweep: ``-O2`` vs ``-O0`` across every registered
    engine × backend combo (Pallas in interpret mode) on adversarial
    forests — bit-exact on quantized, tolerance on float;
  * property suite: every *registered* optimizer pass preserves
    ``predict_oracle`` across the whole adversarial catalog of
    ``tests/test_conformance.py`` (deterministic) and across randomized
    forests (hypothesis, skipped cleanly offline);
  * wiring: plan records, packed round trips of optimized IR, autotuner
    ``opt_levels`` sweeps with cache key-miss hygiene, and cascade
    compatibility (stage splits over the reordered forest, sound
    ``ScoreBoundGate`` exactness).
"""
import numpy as np
import pytest

from repro import core, io, optim
from repro.core import engine_select, registry
from repro.core.quantize import quantize_inputs

from conftest import rand_X
from test_conformance import ADVERSARIAL, QUANTIZABLE, _X

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

COMBOS = [(s.name, s.backend) for s in registry.specs()]
COMBO_IDS = [f"{n}/{b}" for n, b in COMBOS]
JAX_ENGINES = list(registry.engines("jax"))


def _opt_inputs(forest, X):
    """Map caller-coordinate rows into an optimized forest's IR coords
    (what quantize_inputs does on the engine path) for oracle calls."""
    return X if forest.feat_map is None else X[:, forest.feat_map]


# --------------------------------------------------------------------------- #
# framework: registry, levels, resolve_opt
# --------------------------------------------------------------------------- #
def test_registry_has_the_five_passes_and_levels():
    assert set(optim.opt_passes()) >= {
        "compact", "dedup_thresholds", "drop_unused_features",
        "merge_equivalent_leaves", "reorder_trees"}
    assert optim.OPT_LEVELS[0] == ()
    assert set(optim.OPT_LEVELS[1]) < set(optim.OPT_LEVELS[2])
    assert all(n in optim.OPT_PASSES
               for lvl in optim.OPT_LEVELS.values() for n in lvl)


@pytest.mark.parametrize("opt,expect", [
    (None, ((), "O0")), (0, ((), "O0")),
    ("O2", (optim.OPT_LEVELS[2], "O2")),
    ("-O1", (optim.OPT_LEVELS[1], "O1")),
    ("2", (optim.OPT_LEVELS[2], "O2")),
    (("compact",), (("compact",), "compact")),
])
def test_resolve_opt_forms(opt, expect):
    assert optim.resolve_opt(opt) == expect


@pytest.mark.parametrize("bad", ["O9", 7, ("nonesuch",), "fast"])
def test_resolve_opt_rejects_garbage(bad):
    with pytest.raises(ValueError):
        optim.resolve_opt(bad)


def test_optimize_O0_is_identity(small_forest):
    res = optim.optimize(small_forest, 0)
    assert res.forest is small_forest
    assert res.stats == [] and res.verified is None


# --------------------------------------------------------------------------- #
# pass unit tests (hand-built forests, exact expected rewrites)
# --------------------------------------------------------------------------- #
def _pass(name):
    return optim.OPT_PASSES[name].fn


def test_dedup_collapses_dominated_splits():
    """Every node repeats (f=0, t=0.7): the inner splits are decided by
    the outer one, so each 3-node tree collapses to a single split."""
    forest = ADVERSARIAL["duplicate_thresholds"]()
    out = _pass("dedup_thresholds")(forest, {})
    assert int(out.n_nodes.sum()) == forest.n_trees          # 1 per tree
    assert optim.verify_equivalence(forest, out) == "allclose"


def test_dedup_constant_chain_collapses():
    forest = ADVERSARIAL["constant_threshold_chain"]()       # 3-node chain
    out = _pass("dedup_thresholds")(forest, {})
    assert int(out.n_nodes.sum()) == 1
    assert out.max_depth == 2          # from_trees convention: stump = 2


def test_dedup_canonicalizes_negative_zero():
    from repro.trees.cart import Tree, TreeNode
    l = TreeNode(value=np.array([1.0]))
    r = TreeNode(value=np.array([2.0]))
    t0 = Tree(TreeNode(feature=0, threshold=-0.0, left=l, right=r), 2, 1)
    t1 = Tree(TreeNode(feature=0, threshold=0.0,
                       left=TreeNode(value=np.array([1.0])),
                       right=TreeNode(value=np.array([2.0]))), 2, 1)
    forest = core.from_trees([t0, t1], n_features=1, n_classes=1)
    assert optim.n_unique_splits(forest) == 2                # bitwise ≠
    out = _pass("dedup_thresholds")(forest, {})
    assert optim.n_unique_splits(out) == 1                   # canonical
    assert optim.verify_equivalence(forest, out) == "allclose"


def test_dedup_resolves_inf_thresholds():
    forest = ADVERSARIAL["inf_thresholds"]()
    out = _pass("dedup_thresholds")(forest, {})
    # x <= +inf always fires, x <= -inf never (finite inputs): both
    # stumps collapse to their reached leaf
    assert int(out.n_nodes.sum()) < int(forest.n_nodes.sum())
    assert optim.verify_equivalence(forest, out) == "allclose"


def test_merge_equivalent_leaves_folds_constant_subtrees():
    from repro.trees.cart import Tree, TreeNode

    def leaf(v):
        return TreeNode(value=np.array([v]))

    # whole tree is the constant 5.0 → folds to a single leaf bottom-up
    root = TreeNode(feature=0, threshold=0.0,
                    left=TreeNode(feature=1, threshold=1.0,
                                  left=leaf(5.0), right=leaf(5.0)),
                    right=leaf(5.0))
    keep = TreeNode(feature=0, threshold=0.5, left=leaf(1.0),
                    right=leaf(2.0))
    forest = core.from_trees([Tree(root, 3, 2), Tree(keep, 2, 1)],
                             n_features=2, n_classes=1)
    out = _pass("merge_equivalent_leaves")(forest, {})
    assert out.n_nodes.tolist() == [0, 1]
    assert out.n_leaves_per_tree.tolist() == [1, 2]
    assert optim.verify_equivalence(forest, out) == "allclose"


def test_merge_keeps_distinct_leaves():
    forest = ADVERSARIAL["one_tree"]()                       # -1.0 / 1.0
    out = _pass("merge_equivalent_leaves")(forest, {})
    assert int(out.n_nodes.sum()) == int(forest.n_nodes.sum())


def test_compact_shrinks_padding_and_drops_zero_trees():
    from repro.trees.cart import Tree, TreeNode
    deep = TreeNode(feature=0, threshold=0.0,
                    left=TreeNode(feature=1, threshold=-1.0,
                                  left=TreeNode(value=np.array([1.0])),
                                  right=TreeNode(value=np.array([2.0]))),
                    right=TreeNode(value=np.array([3.0])))
    forest = core.from_trees(
        [Tree(TreeNode(value=np.array([0.0])), 1, 0),       # exact zero
         Tree(deep, 3, 2),
         Tree(TreeNode(value=np.array([4.0])), 1, 0)],      # kept constant
        n_features=2, n_classes=1)
    # padding L is inflated to 8 to give compact something to strip
    from repro.optim.rewrite import extract_tree, rebuild_forest
    fat = rebuild_forest(forest, [extract_tree(forest, t)
                                  for t in range(forest.n_trees)],
                         n_leaves=8)
    out = _pass("compact")(fat, {})
    assert out.n_trees == 2                                  # zero dropped
    assert out.n_leaves == 3                                 # L: 8 → 3
    assert optim.verify_equivalence(fat, out) == "allclose"


def test_compact_keeps_one_tree_when_everything_is_zero():
    from repro.trees.cart import Tree, TreeNode
    forest = core.from_trees(
        [Tree(TreeNode(value=np.array([0.0])), 1, 0)] * 3,
        n_features=1, n_classes=1)
    out = _pass("compact")(forest, {})
    assert out.n_trees == 1
    np.testing.assert_array_equal(out.predict_oracle(np.zeros((2, 1))),
                                  [[0.0], [0.0]])


def test_drop_unused_features_remaps_and_keeps_fullwidth_rows():
    forest = ADVERSARIAL["unused_features"]()                # d=8, uses {5}
    out = _pass("drop_unused_features")(forest, {})
    # n_features_in is the true caller-side width (8, recorded at remap
    # time), not the max(feat_map)+1 lower bound (6)
    assert out.n_features == 1 and out.n_features_in == 8
    np.testing.assert_array_equal(out.feat_map, [5])
    X = _X(forest, B=12, seed=3)
    np.testing.assert_array_equal(out.predict_oracle(X[:, out.feat_map]),
                                  forest.predict_oracle(X))
    # the engine path still takes full-width rows (transform remaps)
    pred = core.compile_forest(out, engine="bitvector")
    np.testing.assert_allclose(pred.predict(X), forest.predict_oracle(X),
                               rtol=1e-5, atol=1e-6)


def test_drop_unused_features_composes_with_existing_map():
    forest = ADVERSARIAL["unused_features"]()
    once = _pass("drop_unused_features")(forest, {})
    # artificially re-widen: tack an unused column onto the remapped IR
    import dataclasses
    wide = dataclasses.replace(once, n_features=3,
                               feat_map=np.array([5, 2, 7]),
                               feat_lo=None, feat_hi=None)
    twice = _pass("drop_unused_features")(wide, {})
    np.testing.assert_array_equal(twice.feat_map, [5])       # composed
    assert twice.n_features == 1


def test_quantize_inputs_applies_feat_map_for_float_and_quantized():
    forest = ADVERSARIAL["unused_features"]()
    X = _X(forest, B=8, seed=4)
    out = _pass("drop_unused_features")(forest, {})
    np.testing.assert_array_equal(quantize_inputs(out, X), X[:, [5]])
    qf = core.quantize_forest(forest, X)
    qout = _pass("drop_unused_features")(qf, {})
    np.testing.assert_array_equal(quantize_inputs(qout, X),
                                  quantize_inputs(qf, X)[:, [5]])


def test_quantize_after_drop_unused_aligns_calibration_columns():
    """optimize-then-quantize (the reverse of the pipeline order) must
    calibrate per-feature ranges on the *remapped* columns."""
    forest = ADVERSARIAL["unused_features"]()                # uses col 5
    X = _X(forest, B=32, seed=9)
    dropped = _pass("drop_unused_features")(forest, {})
    q_direct = core.quantize_forest(forest, X)
    q_opt = core.quantize_forest(dropped, X)
    np.testing.assert_array_equal(q_opt.feat_lo, q_direct.feat_lo[[5]])
    np.testing.assert_array_equal(quantize_inputs(q_opt, X),
                                  quantize_inputs(q_direct, X)[:, [5]])
    np.testing.assert_array_equal(
        core.compile_forest(q_opt).predict(X),
        core.compile_forest(q_direct).predict(X))


def test_reorder_trees_puts_discriminative_first():
    from repro.trees.cart import Tree, TreeNode
    const = Tree(TreeNode(value=np.array([0.5, 0.5])), 1, 0)
    disc = Tree(TreeNode(feature=0, threshold=0.0,
                         left=TreeNode(value=np.array([9.0, 0.0])),
                         right=TreeNode(value=np.array([0.0, 9.0]))), 2, 1)
    forest = core.from_trees([const, const, disc], n_features=1,
                             n_classes=2)
    # data-free fallback: leaf spread ranks the split tree first
    out = _pass("reorder_trees")(forest, {})
    assert int(out.n_nodes[0]) == 1 and out.n_nodes[1:].tolist() == [0, 0]
    # validation-set cost model agrees
    X = np.linspace(-1, 1, 32)[:, None]
    out2 = _pass("reorder_trees")(forest, {"X_calib": X})
    assert int(out2.n_nodes[0]) == 1


def test_reorder_is_deterministic_and_stable_on_ties(small_forest):
    a = _pass("reorder_trees")(small_forest, {})
    b = _pass("reorder_trees")(small_forest, {})
    np.testing.assert_array_equal(a.threshold, b.threshold)


def test_per_tree_scores_sum_to_oracle(class_forest):
    X = rand_X(class_forest, B=16)
    S = optim.per_tree_scores(class_forest, X)
    np.testing.assert_allclose(S.sum(axis=0),
                               class_forest.predict_oracle(X),
                               rtol=1e-6, atol=1e-8)


# --------------------------------------------------------------------------- #
# the equivalence contract: verification is mandatory and actually bites
# --------------------------------------------------------------------------- #
def test_verify_catches_a_broken_pass(small_forest):
    @optim.register_pass("_broken", doc="flips a leaf (test only)")
    def _broken(forest, ctx):
        import dataclasses
        lv = forest.leaf_value.copy()
        lv[0, 0] += np.ones_like(lv[0, 0])      # int- and float-safe
        return dataclasses.replace(forest, leaf_value=lv)

    try:
        with pytest.raises(optim.OptimizationError, match="diverges"):
            optim.optimize(small_forest, ("_broken",))
        qf = core.quantize_forest(small_forest,
                                  rand_X(small_forest, B=64))
        with pytest.raises(optim.OptimizationError, match="bit-exact"):
            optim.optimize(qf, ("_broken",))
    finally:
        del optim.OPT_PASSES["_broken"]


def test_optimize_quantized_reports_bitexact(small_forest):
    qf = core.quantize_forest(small_forest, rand_X(small_forest, B=64))
    res = optim.optimize(qf, 2)
    assert res.verified == "bit-exact"
    assert res.tag == "O2" and len(res.stats) == 5


@pytest.mark.parametrize("name", sorted(optim.OPT_LEVELS[2]))
@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_every_pass_preserves_oracle_on_catalog(case, name):
    """The satellite property: every registered optimizer pass preserves
    predict_oracle across the conformance catalog's adversarial forests,
    float and (where possible) quantized."""
    forest = ADVERSARIAL[case]()
    optim.optimize(forest, (name,))          # raises on divergence
    if case in QUANTIZABLE:
        qf = core.quantize_forest(forest, _X(forest, B=16, seed=1))
        res = optim.optimize(qf, (name,))
        assert res.verified == "bit-exact"


# --------------------------------------------------------------------------- #
# -O2 through every registered engine × backend combo (acceptance)
# --------------------------------------------------------------------------- #
def _compile(forest, name, backend, **kw):
    return core.compile_forest(forest, engine=name, backend=backend, **kw)


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
def test_O2_matches_O0_for_every_engine_backend(name, backend):
    forest = ADVERSARIAL["mixed_stump_and_deep"]()
    X = _X(forest, B=12, seed=5)
    qf = core.quantize_forest(forest, X)
    q0 = _compile(qf, name, backend)
    q2 = _compile(qf, name, backend, opt=2)
    np.testing.assert_array_equal(q2.predict(X), q0.predict(X),
                                  err_msg=f"{name}/{backend} quantized")
    f0 = _compile(forest, name, backend)
    f2 = _compile(forest, name, backend, opt=2)
    np.testing.assert_allclose(f2.predict(X), f0.predict(X),
                               rtol=1e-5, atol=1e-6,
                               err_msg=f"{name}/{backend} float")


@pytest.mark.parametrize("engine", JAX_ENGINES)
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_O2_quantized_bitexact_across_catalog(case, engine):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=6)
    qf = core.quantize_forest(forest, X)
    p0 = _compile(qf, engine, "jax")
    p2 = _compile(qf, engine, "jax", opt=2)
    np.testing.assert_array_equal(p2.predict(X), p0.predict(X),
                                  err_msg=f"{case}/{engine}")


# --------------------------------------------------------------------------- #
# pipeline plan records
# --------------------------------------------------------------------------- #
def test_plan_records_optimizer_passes(small_forest):
    pred = core.compile_forest(small_forest, engine="bitvector", opt=2)
    names = [r.name for r in pred.plan.records]
    for p in optim.OPT_LEVELS[2]:
        assert f"opt.{p}" in names
    assert "optimize" in names
    d = pred.plan.describe()
    assert "O2" in d and "verified" in d and "nodes" in d


def test_plan_O0_keeps_single_skipped_record(small_forest):
    from repro.core.pipeline import PIPELINE
    pred = core.compile_forest(small_forest, engine="bitvector")
    assert [r.name for r in pred.plan.records] == list(PIPELINE)
    rec = [r for r in pred.plan.records if r.name == "optimize"][0]
    assert "skipped" in rec.detail


# --------------------------------------------------------------------------- #
# packed serialization of optimized IR (headers + feat_map round trip)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("case", QUANTIZABLE)
def test_optimized_forest_roundtrip(case, tmp_path):
    forest = ADVERSARIAL[case]()
    X = _X(forest, B=12, seed=7)
    qf = core.quantize_forest(forest, X)
    of = optim.optimize(qf, 2).forest
    p = str(tmp_path / "opt.repro.npz")
    io.save_forest(of, p)
    loaded = io.load_forest(p)
    if of.feat_map is None:
        assert loaded.feat_map is None
    else:
        np.testing.assert_array_equal(loaded.feat_map, of.feat_map)
        assert io.peek(p)["forest"]["n_features_in"] == of.n_features_in
    np.testing.assert_array_equal(quantize_inputs(loaded, X),
                                  quantize_inputs(of, X))
    Xq = quantize_inputs(of, X)
    np.testing.assert_array_equal(loaded.predict_oracle(Xq),
                                  of.predict_oracle(Xq))


@pytest.mark.parametrize("engine", JAX_ENGINES)
def test_optimized_predictor_artifact_roundtrip(engine, tmp_path):
    """compile -O2 → save → load → predict is bit-identical, optimizer
    plan records included (the artifact can explain how it was built)."""
    forest = ADVERSARIAL["unused_features"]()
    X = _X(forest, B=10, seed=8)
    qf = core.quantize_forest(forest, X)
    pred = core.compile_forest(qf, engine=engine, opt=2)
    p = str(tmp_path / "pred.repro.npz")
    io.save_predictor(pred, p)
    loaded = io.load_predictor(p)
    np.testing.assert_array_equal(pred.predict(X), loaded.predict(X),
                                  err_msg=engine)
    names = [r.name for r in loaded.plan.records]
    assert any(n.startswith("opt.") for n in names)


# --------------------------------------------------------------------------- #
# autotuner opt_levels sweeps + cache hygiene
# --------------------------------------------------------------------------- #
@pytest.fixture(autouse=True)
def _fresh_cache():
    engine_select.clear_cache()
    yield
    engine_select.clear_cache()


def test_autotuner_opt_sweep(small_forest):
    c = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                             opt_levels=(1, 2), cache_path=None, repeats=1)
    assert set(c.timings) == {"qs", "qs@O1", "qs@O2",
                              "native", "native@O1", "native@O2"}
    assert c.engine == min(c.timings, key=c.timings.get)
    X = rand_X(small_forest, B=16)
    np.testing.assert_allclose(c.predict(X),
                               small_forest.predict_oracle(X),
                               rtol=1e-4, atol=1e-5)
    # the winner carries a plan that names its variant
    plan = c.predictor.plan
    if c.engine.endswith("@O2"):
        assert any(r.name == "optimize" and "O2" in r.detail
                   for r in plan.records)


def test_autotuner_opt_sweep_composes_with_quant(small_forest):
    c = engine_select.choose(small_forest, 16, engines=("native",),
                             quant_specs=(core.QuantSpec(bits=16),),
                             opt_levels=(2,), cache_path=None, repeats=1)
    assert set(c.timings) == {"native", "native@O2", "native@q16",
                              "native@q16@O2"}


def test_old_cache_entries_keymiss_opt_sweeps(small_forest, tmp_path):
    """The acceptance invariant: an entry written before the opt axis
    existed must key-miss an opt-level sweep (partial re-bench), never
    answer for it."""
    import json
    cache = str(tmp_path / "engines.json")
    plain = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                                 cache_path=cache, repeats=1)
    engine_select.clear_cache()              # fresh process, disk only
    c = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                             opt_levels=(2,), cache_path=cache, repeats=1)
    assert not c.from_cache
    assert c.timings["qs"] == plain.timings["qs"]    # reused, not re-run
    assert set(c.timings) == {"qs", "native", "qs@O2", "native@O2"}
    # widened entry answers both request shapes now
    assert engine_select.choose(small_forest, 16, engines=("qs", "native"),
                                opt_levels=(2,), cache_path=cache,
                                repeats=1).from_cache
    assert engine_select.choose(small_forest, 16, engines=("qs", "native"),
                                cache_path=cache, repeats=1).from_cache
    with open(cache) as f:
        entry = json.load(f)[plain.key]
    assert set(entry["timings"]) == set(c.timings)


def test_opt_sweep_rejects_garbage_level(small_forest):
    with pytest.raises(ValueError, match="opt level"):
        engine_select.choose(small_forest, 16, engines=("qs",),
                             opt_levels=("O9",), cache_path=None,
                             repeats=1)


def test_server_serves_opt_winner(small_forest, tmp_path):
    from repro.inference.server import ForestServer
    srv = ForestServer.from_forest(small_forest, max_batch=8,
                                   engines=("qs",), opt_levels=(2,),
                                   cache_path=str(tmp_path / "c.json"),
                                   repeats=1)
    assert srv.engine_choice.engine in {"qs", "qs@O2"}
    rng = np.random.default_rng(0)
    for i in range(8):
        srv.submit(rng.normal(size=small_forest.n_features),
                   arrival_s=float(i) * 1e-4)
    done = srv.flush(now_s=1.0)
    assert len(done) == 8


# --------------------------------------------------------------------------- #
# cascade compatibility: stage splits see the reordered forest
# --------------------------------------------------------------------------- #
def test_cascade_over_O2_forest_scoreboundgate_exact(class_forest):
    """A cascade over the optimized (reordered) forest with the sound
    bound gate keeps predict_class equal to the -O0 full forest."""
    from repro.cascade import CascadePredictor, CascadeSpec, ScoreBoundGate
    X = rand_X(class_forest, B=48)
    qf = core.quantize_forest(class_forest, X)
    of = optim.optimize(qf, 2).forest
    base = core.compile_forest(qf, engine="bitvector")
    casc = CascadePredictor(
        of, CascadeSpec((max(of.n_trees // 3, 1), of.n_trees),
                        ScoreBoundGate()), engine="bitvector")
    np.testing.assert_array_equal(casc.predict_class(X),
                                  base.predict_class(X))


def test_pipeline_opt_plus_cascade_stages_split_optimized_forest(
        class_forest):
    from repro.cascade import CascadeSpec, MarginGate
    X = rand_X(class_forest, B=32)
    qf = core.quantize_forest(class_forest, X)
    of = optim.optimize(qf, 2).forest
    pred = core.compile_forest(
        qf, engine="bitvector", opt=2,
        cascade=CascadeSpec((4, qf.n_trees), MarginGate(np.inf)))
    # the cascade's host forest is the optimized one (reordered trees)
    np.testing.assert_array_equal(pred.host_forest().threshold,
                                  of.threshold)
    base = core.compile_forest(qf, engine="bitvector")
    np.testing.assert_array_equal(pred.predict(X), base.predict(X))


def test_reorder_improves_bound_gate_exits():
    """Discriminative-first ordering lets the sound gate exit rows no
    later than the worst ordering (the pass's whole point)."""
    from repro.cascade import CascadePredictor, CascadeSpec, ScoreBoundGate
    rng = np.random.default_rng(5)
    from repro.trees.cart import Tree, TreeNode
    trees = []
    for i in range(8):       # weak (near-zero) trees first by construction
        v = 0.01 if i < 6 else 5.0
        trees.append(Tree(TreeNode(
            feature=0, threshold=float(rng.normal()),
            left=TreeNode(value=np.array([v, 0.0])),
            right=TreeNode(value=np.array([0.0, v]))), 2, 1))
    forest = core.from_trees(trees, n_features=1, n_classes=2)
    X = rng.normal(0, 1, size=(64, 1))
    stages = (4, 8)

    def mean_trees(f):
        casc = CascadePredictor(f, CascadeSpec(stages, ScoreBoundGate()),
                                engine="bitvector")
        casc.predict(X)
        return casc.mean_trees_evaluated

    plain = mean_trees(forest)
    ordered = mean_trees(_pass("reorder_trees")(forest, {"X_calib": X}))
    assert ordered <= plain
    assert ordered < forest.n_trees          # some rows actually exit


# --------------------------------------------------------------------------- #
# shared analysis (rapidscorer consumes the optimizer's unique_splits)
# --------------------------------------------------------------------------- #
def test_merge_nodes_delegates_to_optim_analysis(small_forest):
    uf, ut, inv, n = core.merge_nodes(small_forest)
    uf2, ut2, inv2, n2 = optim.unique_splits(small_forest)
    np.testing.assert_array_equal(uf, uf2)
    np.testing.assert_array_equal(ut, ut2)
    np.testing.assert_array_equal(inv, inv2)
    assert n == n2
    assert core.merge_stats(small_forest) == \
        optim.unique_fraction(small_forest)


# --------------------------------------------------------------------------- #
# hypothesis: randomized adversarial forests (CI; skipped offline)
# --------------------------------------------------------------------------- #
if HAVE_HYPOTHESIS:
    from test_conformance import adversarial_forests, _widen

    @settings(max_examples=20, deadline=None)
    @given(adversarial_forests(), st.sampled_from(sorted(optim.OPT_PASSES)),
           st.integers(0, 9999))
    def test_hypothesis_every_pass_preserves_oracle(af, name, xseed):
        base, d_total, n_stumps, seed = af
        forest = _widen(base, d_total, n_stumps, seed)
        optim.optimize(forest, (name,), seed=xseed)   # raises on breakage
        qf = core.quantize_forest(
            forest, np.random.default_rng(xseed).normal(
                0, 2.0, size=(16, d_total)))
        res = optim.optimize(qf, (name,), seed=xseed)
        assert res.verified == "bit-exact"

    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 10), st.integers(1, 8), st.integers(0, 9999))
    def test_hypothesis_O2_cascade_bound_gate_exact(T, k, xseed):
        from repro.cascade import CascadePredictor, CascadeSpec, \
            ScoreBoundGate
        forest = core.random_forest_ir(T, 8, 4, n_classes=2,
                                       seed=xseed % 89, full=False)
        X = np.random.default_rng(xseed).normal(0, 2.0, size=(24, 4))
        qf = core.quantize_forest(forest, X)
        of = optim.optimize(qf, 2).forest
        base = core.compile_forest(qf, engine="bitvector")
        casc = CascadePredictor(
            of, CascadeSpec((min(k, of.n_trees), of.n_trees),
                            ScoreBoundGate()), engine="bitvector")
        np.testing.assert_array_equal(casc.predict_class(X),
                                      base.predict_class(X))
