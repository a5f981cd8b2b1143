"""Engine equivalence: every *registered* engine ≡ the oracles, float and
quantized, scalar and multiclass, single- and multi-word.

The parametrization is sourced from ``core.registry`` — registering a new
engine automatically enrolls it in the shared agreement suite below
(engine × backend × float/quantized vs ``eval_scalar_numpy``)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import registry
from repro.core.quickscorer import (compile_qs, ctz32, eval_batch,
                                    eval_scalar_numpy, exit_leaf)
from repro.core.rapidscorer import compile_rs, eval_batch as rs_eval

from conftest import rand_X

ENGINES = list(registry.engines("jax"))
COMBOS = [(s.name, s.backend) for s in registry.specs()]
COMBO_IDS = [f"{n}/{b}" for n, b in COMBOS]


def _compile(forest, name, backend):
    return core.compile_forest(forest, engine=name, backend=backend)


def scalar_oracle_f32(forest, X_raw):
    """``eval_scalar_numpy`` recast to the engines' float32 arithmetic.

    For quantized forests both sides compute exact integer leaf sums and
    divide by the same power-of-two scale, so the comparison is bitwise."""
    Xq = core.quantize_inputs(forest, np.asarray(X_raw))
    s = core.leaf_scale(forest)
    raw = eval_scalar_numpy(forest, Xq) * s        # exact int sums (f64)
    return raw.astype(np.float32) / np.float32(s)


# --------------------------------------------------------------------------- #
# bit helpers
# --------------------------------------------------------------------------- #
def test_ctz32_exhaustive_bits():
    for b in range(32):
        w = jnp.uint32(1 << b)
        assert int(ctz32(w)) == b


def test_ctz32_composite():
    assert int(ctz32(jnp.uint32(0b101000))) == 3
    assert int(ctz32(jnp.uint32(0xFFFFFFFF))) == 0


def test_exit_leaf_multiword():
    # word 0 empty, word 1 has bit 5 → leaf 37
    idx = jnp.asarray(np.array([[0, 1 << 5]], dtype=np.uint32))
    assert int(exit_leaf(idx)[0]) == 37
    idx = jnp.asarray(np.array([[1 << 31, 1 << 5]], dtype=np.uint32))
    assert int(exit_leaf(idx)[0]) == 31


# --------------------------------------------------------------------------- #
# shared agreement suite: every registered (engine × backend) combination
# vs the faithful scalar QuickScorer, float AND quantized
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="session")
def quant_forest(small_forest):
    """small_forest quantized with the paper-default 16-bit spec (all
    scales are powers of two → engine outputs must be bit-exact)."""
    return core.quantize_forest(small_forest,
                                rand_X(small_forest, B=256, seed=9))


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
def test_engine_float_agrees_with_scalar_oracle(name, backend, small_forest):
    X = rand_X(small_forest, B=12)
    pred = _compile(small_forest, name, backend)
    expect = eval_scalar_numpy(small_forest, X)
    np.testing.assert_allclose(pred.predict(X), expect, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
def test_engine_quantized_bitexact_vs_scalar_oracle(name, backend,
                                                    quant_forest):
    X = rand_X(quant_forest, B=12, seed=7)
    pred = _compile(quant_forest, name, backend)
    expect = scalar_oracle_f32(quant_forest, X)
    np.testing.assert_array_equal(pred.predict(X), expect)


# --------------------------------------------------------------------------- #
# engines vs the vectorized traversal oracle across forest shapes
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("fixture", ["small_forest", "class_forest",
                                     "big_leaf_forest"])
def test_engine_matches_oracle(engine, fixture, request):
    forest = request.getfixturevalue(fixture)
    X = rand_X(forest, B=96)
    pred = core.compile_forest(forest, engine=engine)
    expect = forest.predict_oracle(X)
    got = pred.predict(X)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_quantized_matches_quantized_oracle(engine, trained_rf,
                                                   magic_ds):
    forest = core.from_random_forest(trained_rf)
    qf = core.quantize_forest(forest, magic_ds.X_train)
    X = magic_ds.X_test[:96]
    pred = core.compile_forest(qf, engine=engine)
    got = pred.predict(X)
    from repro.kernels.ref import ref_oracle
    expect = ref_oracle(qf, X)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_scalar_qs_matches_batch(small_forest):
    """Faithful Algorithm 1 (sorted features, early break) ≡ predicated
    batch evaluation — validates the DESIGN.md §2.1 predication claim."""
    X = rand_X(small_forest, B=16)
    scalar = eval_scalar_numpy(small_forest, X)
    batch = np.asarray(eval_batch(compile_qs(small_forest),
                                  jnp.asarray(X)))
    np.testing.assert_allclose(scalar, batch, rtol=1e-5, atol=1e-6)


def test_rapidscorer_equals_quickscorer(class_forest):
    X = rand_X(class_forest, B=48)
    qs = np.asarray(eval_batch(compile_qs(class_forest), jnp.asarray(X)))
    rs = np.asarray(rs_eval(compile_rs(class_forest), jnp.asarray(X)))
    np.testing.assert_allclose(qs, rs, rtol=1e-6)


def test_merging_reduces_unique_nodes(trained_rf):
    """RF trees share thresholds (binned training) → merging must help."""
    forest = core.from_random_forest(trained_rf)
    frac = core.merge_stats(forest)
    assert 0.0 < frac < 1.0


def test_merge_idempotent_on_distinct_nodes():
    f = core.random_forest_ir(4, 8, 4, seed=11)
    # continuous random thresholds: collisions ~impossible
    frac = core.merge_stats(f)
    assert frac == pytest.approx(1.0)


def test_threshold_boundary_exact():
    """x == t must go LEFT (predicate is x > t for the mask)."""
    from repro.trees.cart import Tree, TreeNode
    l0 = TreeNode(value=np.array([1.0]))
    l1 = TreeNode(value=np.array([2.0]))
    root = TreeNode(feature=0, threshold=0.5, left=l0, right=l1)
    f = core.from_trees([Tree(root, 2, 1)], n_features=1, n_classes=1)
    X = np.array([[0.5], [0.5 + 1e-6]])
    for engine in ENGINES:
        got = core.compile_forest(f, engine=engine).predict(X)
        np.testing.assert_allclose(got[:, 0], [1.0, 2.0], rtol=1e-6,
                                   err_msg=engine)


def test_single_leaf_tree():
    """Degenerate trees (no splits) must contribute their constant."""
    from repro.trees.cart import Tree, TreeNode
    stump = Tree(TreeNode(value=np.array([7.0])), 1, 0)
    l0 = TreeNode(value=np.array([1.0]))
    l1 = TreeNode(value=np.array([2.0]))
    real = Tree(TreeNode(feature=0, threshold=0.0, left=l0, right=l1), 2, 1)
    f = core.from_trees([stump, real], n_features=1, n_classes=1)
    X = np.array([[-1.0], [1.0]])
    expect = np.array([[8.0], [9.0]])
    for engine in ENGINES:
        got = core.compile_forest(f, engine=engine).predict(X)
        np.testing.assert_allclose(got, expect, rtol=1e-6, err_msg=engine)


def test_gbt_forest_roundtrip(magic_ds):
    from repro.trees.gradient_boosting import (GradientBoosting,
                                               GradientBoostingConfig)
    gb = GradientBoosting(GradientBoostingConfig(
        n_trees=20, max_leaves=8, objective="l2", seed=0)).fit(
        magic_ds.X_train, magic_ds.y_train.astype(np.float64))
    forest = core.from_gradient_boosting(gb)
    X = magic_ds.X_test[:64]
    direct = gb.predict(X)
    via_ir = forest.predict_oracle(X)[:, 0]
    np.testing.assert_allclose(via_ir, direct, rtol=1e-6, atol=1e-8)
    for engine in ENGINES:
        got = core.compile_forest(forest, engine=engine).predict(X)[:, 0]
        np.testing.assert_allclose(got, direct, rtol=1e-4, atol=1e-5,
                                   err_msg=engine)


def test_softmax_gbt_class_embedding(magic_ds):
    from repro.trees.gradient_boosting import (GradientBoosting,
                                               GradientBoostingConfig)
    gb = GradientBoosting(GradientBoostingConfig(
        n_trees=12, max_leaves=8, objective="softmax", seed=0)).fit(
        magic_ds.X_train, magic_ds.y_train)
    forest = core.from_gradient_boosting(gb)
    assert forest.n_classes == 2
    X = magic_ds.X_test[:64]
    np.testing.assert_allclose(forest.predict_oracle(X), gb.predict(X),
                               rtol=1e-6, atol=1e-8)


# --------------------------------------------------------------------------- #
# trees wider than 64 leaves: 255 leaves (8 mask words, the last one
# part-filled), and 33-, 64- and 255-leaf trees in one forest, padded to
# 255; every engine, the Pallas kernels in the interpreter at their own
# block sizes (two tree tiles, two row blocks), against the oracle
# --------------------------------------------------------------------------- #
WIDE_T, WIDE_D, WIDE_B = 16, 136, 160


@pytest.fixture(scope="module", params=["255", "33-64-255"])
def wide_forest(request):
    from repro.core.forest import _random_tree, from_trees
    sizes = {"255": [255], "33-64-255": [33, 64, 255]}[request.param]
    rng = np.random.default_rng(255)
    f = from_trees([_random_tree(rng, sizes[t % len(sizes)], WIDE_D, 1,
                                 False) for t in range(WIDE_T)], WIDE_D, 1)
    assert (f.n_leaves, f.n_words) == (255, 8)
    return f


@pytest.mark.parametrize("quant", [None, 16], ids=["float32", "int16"])
@pytest.mark.parametrize("name,backend", COMBOS, ids=COMBO_IDS)
def test_wide_trees_every_engine_matches_oracle(name, backend, quant,
                                                wide_forest):
    # float32 rows: the oracle compares the very values the engines see,
    # standard normal as the thresholds, so every split sees both sides
    X = np.random.default_rng(7).standard_normal((WIDE_B, WIDE_D),
                                                 dtype=np.float32)
    forest = wide_forest if quant is None else core.quantize_forest(
        wide_forest, X, core.QuantSpec(bits=quant))
    got = registry.build(forest, name, backend).predict(X)
    if quant is None:
        # float32 sums of the trees' leaves, in another order than the
        # float64 oracle's: each of at most T additions rounds by at most
        # 2^-24 of a partial sum, which is below Σ_t max|leaf_t|
        lv = np.abs(forest.leaf_value).max(axis=(1, 2))
        tol = WIDE_T * 2.0 ** -24 * lv.sum()
        np.testing.assert_allclose(got, forest.predict_oracle(X), rtol=0,
                                   atol=tol)
    else:
        # integer leaf sums are exact (below 2^24) and the descale is a
        # power of two: equal bit for bit
        Xq = core.quantize_inputs(forest, X)
        want = forest.predict_oracle(Xq).astype(np.float32) \
            / np.float32(core.leaf_scale(forest))
        np.testing.assert_array_equal(got, want)
