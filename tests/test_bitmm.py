"""Bit-matmul engine validation: XLA path and Pallas kernel vs the
faithful scalar QuickScorer (Algorithm 1) and the traversal oracle, on
float32 and quantized (int16/int8) forests including the edge shapes the
packing has to survive: deep unbalanced trees (wide count fields), stumps,
multiclass, single-leaf trees, and multi-word leaf counts."""
import numpy as np
import pytest

from repro import core
from repro.core.quickscorer import (compile_qs, compile_qs_bitmm,
                                    eval_batch, eval_batch_bitmm,
                                    eval_scalar_numpy)
from repro.core.quantize import QuantSpec, quantize_forest, quantize_inputs
from repro.kernels.ops import pallas_bitmm_predictor

import jax.numpy as jnp

from conftest import rand_X

FOREST_SWEEP = [
    # (n_trees, n_leaves, n_features, n_classes, full, seed)
    (8, 16, 6, 1, True, 0),        # balanced
    (6, 64, 8, 1, False, 1),       # deep/unbalanced, multi-word counts
    (12, 32, 10, 3, False, 2),     # multiclass
    (10, 2, 4, 1, True, 3),        # stumps (single split per tree)
    (4, 128, 5, 2, False, 4),      # very deep, wide leaf axis
]


def _forest(T, L, d, C, full, seed):
    return core.random_forest_ir(T, L, d, n_classes=C, seed=seed, full=full)


@pytest.mark.parametrize("T,L,d,C,full,seed", FOREST_SWEEP)
def test_bitmm_matches_scalar_qs(T, L, d, C, full, seed):
    """eval_batch_bitmm ≡ Algorithm 1 (sorted features, early break)."""
    forest = _forest(T, L, d, C, full, seed)
    X = rand_X(forest, B=8, seed=seed + 100)
    scalar = eval_scalar_numpy(forest, X)
    got = np.asarray(eval_batch_bitmm(compile_qs_bitmm(forest),
                                      jnp.asarray(X)))
    np.testing.assert_allclose(got, scalar, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T,L,d,C,full,seed", FOREST_SWEEP)
def test_pallas_bitmm_matches_scalar_qs(T, L, d, C, full, seed):
    forest = _forest(T, L, d, C, full, seed)
    X = rand_X(forest, B=8, seed=seed + 200)
    scalar = eval_scalar_numpy(forest, X)
    pred = pallas_bitmm_predictor(forest, block_b=8, block_t=4)
    np.testing.assert_allclose(pred.predict(X), scalar, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("T,L,d,C,full,seed", FOREST_SWEEP)
def test_bitmm_matches_eval_batch_larger_batch(T, L, d, C, full, seed):
    """Against the seed XLA engine on a bigger batch (cheap oracle)."""
    forest = _forest(T, L, d, C, full, seed)
    X = jnp.asarray(rand_X(forest, B=96, seed=seed + 300))
    ref = np.asarray(eval_batch(compile_qs(forest), X))
    got = np.asarray(eval_batch_bitmm(compile_qs_bitmm(forest), X))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bits", [16, 8])
def test_bitmm_quantized_exact(bits, trained_rf, magic_ds):
    """Quantized forests: integer thresholds and leaves → bit-exact
    agreement with the scalar oracle (all arithmetic stays below 2^24)."""
    forest = core.from_random_forest(trained_rf)
    qf = quantize_forest(forest, magic_ds.X_train, spec=QuantSpec(bits=bits))
    X = magic_ds.X_test[:48]
    Xq = quantize_inputs(qf, X)
    scalar = eval_scalar_numpy(qf, Xq)
    got = core.compile_forest(qf, engine="bitmm").predict(X)
    np.testing.assert_array_equal(got, scalar)
    pal = pallas_bitmm_predictor(qf, block_b=16, block_t=8).predict(X)
    np.testing.assert_array_equal(pal, scalar)


def test_bitmm_single_leaf_tree():
    """Degenerate no-split trees must contribute their constant."""
    from repro.trees.cart import Tree, TreeNode
    stump = Tree(TreeNode(value=np.array([7.0])), 1, 0)
    l0, l1 = TreeNode(value=np.array([1.0])), TreeNode(value=np.array([2.0]))
    real = Tree(TreeNode(feature=0, threshold=0.0, left=l0, right=l1), 2, 1)
    f = core.from_trees([stump, real], n_features=1, n_classes=1)
    X = np.array([[-1.0], [1.0]])
    expect = np.array([[8.0], [9.0]])
    got = core.compile_forest(f, engine="bitmm").predict(X)
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    pal = pallas_bitmm_predictor(f, block_b=8, block_t=2).predict(X)
    np.testing.assert_allclose(pal, expect, rtol=1e-6)


def test_bitmm_threshold_boundary_exact():
    """x == t must go LEFT (predicate is x > t for the clear matmul)."""
    from repro.trees.cart import Tree, TreeNode
    l0, l1 = TreeNode(value=np.array([1.0])), TreeNode(value=np.array([2.0]))
    root = TreeNode(feature=0, threshold=0.5, left=l0, right=l1)
    f = core.from_trees([Tree(root, 2, 1)], n_features=1, n_classes=1)
    X = np.array([[0.5], [0.5 + 1e-6]])
    got = core.compile_forest(f, engine="bitmm").predict(X)
    np.testing.assert_allclose(got[:, 0], [1.0, 2.0], rtol=1e-6)


def test_bitmm_tree_chunking_invariant(big_leaf_forest):
    """Scanned tree tiles must not change the result (and padded dummy
    trees must contribute exactly nothing)."""
    X = rand_X(big_leaf_forest, B=40, seed=9)
    ref = np.asarray(eval_batch(compile_qs(big_leaf_forest),
                                jnp.asarray(X)))
    for chunk in (1, 2, 4, big_leaf_forest.n_trees):
        bm = compile_qs_bitmm(big_leaf_forest, tree_chunk=chunk)
        got = np.asarray(eval_batch_bitmm(bm, jnp.asarray(X)))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"tree_chunk={chunk}")


def test_bitmm_field_width_adapts_to_depth():
    """Deep chains need wide count fields; balanced trees pack 8/word."""
    balanced = core.random_forest_ir(4, 64, 6, seed=0, full=True)
    deep = core.random_forest_ir(4, 64, 6, seed=1, full=False)
    bmb = compile_qs_bitmm(balanced)
    bmd = compile_qs_bitmm(deep)
    assert bmb.bits * bmb.npack <= 24 and bmd.bits * bmd.npack <= 24
    assert bmd.bits >= bmb.bits        # deeper → larger max clear count
