"""Pallas kernel validation (interpret mode): shape/dtype sweep, each cell
asserted allclose against the pure-jnp ref.py oracle AND the numpy
traversal oracle."""
import numpy as np
import pytest

from repro import core
from repro.core.quantize import QuantSpec, quantize_forest
from repro.kernels.ops import (pallas_bitmm_predictor, pallas_gemm_predictor,
                              pallas_qs_predictor)
from repro.kernels.ref import ref_gemm, ref_oracle, ref_qs

SHAPE_SWEEP = [
    # (n_trees, n_leaves, n_features, n_classes, batch)
    (4, 8, 4, 1, 16),
    (8, 16, 6, 1, 64),
    (12, 32, 10, 3, 96),
    (6, 64, 8, 2, 33),          # multi-word leafidx + ragged batch
    (16, 32, 784, 10, 40),      # wide features (mnist-like)
    (3, 16, 5, 1, 1),           # single instance
]


def _forest(T, L, d, C, seed=0):
    return core.random_forest_ir(T, L, d, n_classes=C, seed=seed,
                                 full=(seed % 2 == 0))


@pytest.mark.parametrize("T,L,d,C,B", SHAPE_SWEEP)
def test_pallas_qs_matches_ref(T, L, d, C, B):
    forest = _forest(T, L, d, C, seed=T)
    X = np.random.default_rng(B).normal(0, 1.3, size=(B, d))
    pred = pallas_qs_predictor(forest, block_b=32, block_t=4)
    got = pred.predict(X)
    np.testing.assert_allclose(got, ref_qs(forest, X), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, ref_oracle(forest, X), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("T,L,d,C,B", SHAPE_SWEEP[:4])
def test_pallas_gemm_matches_ref(T, L, d, C, B):
    forest = _forest(T, L, d, C, seed=T + 1)
    X = np.random.default_rng(B + 1).normal(0, 1.3, size=(B, d))
    pred = pallas_gemm_predictor(forest, block_b=32, block_t=4)
    got = pred.predict(X)
    np.testing.assert_allclose(got, ref_gemm(forest, X), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got, ref_oracle(forest, X), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("bits", [16, 8])
def test_pallas_qs_quantized(bits, trained_rf, magic_ds):
    forest = core.from_random_forest(trained_rf)
    qf = quantize_forest(forest, magic_ds.X_train, spec=QuantSpec(bits=bits))
    X = magic_ds.X_test[:64]
    pred = pallas_qs_predictor(qf, block_b=32, block_t=8)
    got = pred.predict(X)
    np.testing.assert_allclose(got, ref_oracle(qf, X), rtol=1e-5, atol=1e-6)


def test_pallas_block_shape_independence(small_forest):
    """Result must not depend on the BlockSpec tiling."""
    X = np.random.default_rng(7).normal(size=(70, small_forest.n_features))
    ref = ref_qs(small_forest, X)
    for bb, bt in [(8, 2), (32, 4), (128, 8)]:
        got = pallas_qs_predictor(small_forest, block_b=bb,
                                  block_t=bt).predict(X)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"block_b={bb} block_t={bt}")


def test_pallas_padding_batch_edge(small_forest):
    """Batch not a multiple of block_b: padded rows must not leak."""
    rng = np.random.default_rng(8)
    X = rng.normal(size=(5, small_forest.n_features))
    got = pallas_qs_predictor(small_forest, block_b=64).predict(X)
    assert got.shape == (5, 1)
    np.testing.assert_allclose(got, ref_qs(small_forest, X), rtol=1e-5,
                               atol=1e-6)


def test_pallas_tree_padding(class_forest):
    """Tree count not a multiple of block_t: zero-leaf padding trees must
    contribute exactly nothing."""
    X = np.random.default_rng(9).normal(size=(16, class_forest.n_features))
    got = pallas_qs_predictor(class_forest, block_b=16,
                              block_t=8).predict(X)   # 12 trees → pad to 16
    np.testing.assert_allclose(got, ref_qs(class_forest, X), rtol=1e-5,
                               atol=1e-6)


# --------------------------------------------------------------------------- #
# Threshold folding: float32 rows of a quantized forest skip host
# quantization and run against folded cutoffs (docs/QUANT.md)
# --------------------------------------------------------------------------- #
FOLD_ENGINES = {"pallas-qs": (pallas_qs_predictor, ref_qs),
                 "pallas-bitmm": (pallas_bitmm_predictor, ref_qs),
                 "pallas-gemm": (pallas_gemm_predictor, ref_gemm)}
FOLD_D = 10


def _qforest(int_accum=False, bits=16):
    f = core.random_forest_ir(12, 16, FOLD_D, n_classes=3, seed=4,
                              full=False)
    X = np.random.default_rng(4).normal(size=(512, FOLD_D))
    return quantize_forest(f, X, QuantSpec(bits=bits, int_accum=int_accum))


def _rows32(B=70, seed=6):
    return np.random.default_rng(seed).normal(
        0, 1.3, size=(B, FOLD_D)).astype(np.float32)


@pytest.mark.parametrize("int_accum", [False, True],
                         ids=["f32-accum", "int-accum"])
@pytest.mark.parametrize("engine", sorted(FOLD_ENGINES))
def test_pallas_folded_rows_match_host_quantized(engine, int_accum):
    """float32 rows (folded) and the same rows as float64 (host
    quantization, integer thresholds) give the same scores bit for bit,
    and the jnp reference's."""
    build, ref = FOLD_ENGINES[engine]
    qf = _qforest(int_accum)
    X = _rows32()
    pred = build(qf, block_b=32, block_t=4)
    assert pred.folds_inputs(X)
    assert not pred.folds_inputs(X.astype(np.float64))
    folded = pred.predict(X)
    host = pred.predict(X.astype(np.float64))
    np.testing.assert_array_equal(folded, host)
    np.testing.assert_array_equal(folded, ref(qf, X))
    assert set(pred._programs) == {True, False}
    assert pred.n_compiles == 2          # one bucket, two programs


@pytest.mark.parametrize("engine", sorted(FOLD_ENGINES))
def test_pallas_folded_nonfinite_rows_match_host(engine):
    build, _ = FOLD_ENGINES[engine]
    qf = _qforest()
    X = _rows32(B=8)
    X[0] = np.nan
    X[1, ::2], X[1, 1::2] = np.inf, -np.inf
    X[2, :3] = [np.nan, np.inf, -np.inf]
    X[3] = [1e30, -1e30, 0.0, -0.0, 1e-45, -1e-45, 3.0, -3.0, 1e-38,
            -1e-38]
    pred = build(qf, block_b=32, block_t=4)
    with np.errstate(invalid="ignore"):
        host = pred.predict(X.astype(np.float64))
    got = pred.predict(X)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, host)


@pytest.mark.parametrize("dtype", ["float16", "int8", "uint8", "int16",
                                   "uint16", "int32", "float64"])
def test_pallas_row_dtype_chooses_the_program(dtype):
    """Rows float32 holds exactly fold; wider ones are quantized on the
    host and stay on the integer grid.  Either way the scores are the
    host path's."""
    qf = _qforest()
    rng = np.random.default_rng(7)
    X = (rng.normal(0, 2, size=(20, FOLD_D)) *
         (1 if dtype.startswith("float") else 1.5)).astype(dtype)
    pred = pallas_qs_predictor(qf, block_b=32, block_t=4)
    folds = dtype not in ("int32", "float64")
    assert pred.folds_inputs(X) == folds
    Xq = pred.transform_inputs(X)
    assert Xq.dtype == (np.float32 if folds else np.int16)
    np.testing.assert_array_equal(pred.predict(X),
                                  ref_qs(qf, X.astype(np.float64)))
    assert set(pred._programs) == {folds}


def test_pallas_staged_cascade_quantized_scores_as_before():
    """A staged cascade hands its stages the shared int16 matrix: they
    take the integer-threshold program, and with the gate held open the
    cascade scores as the whole forest does."""
    from repro.cascade import CascadePredictor, CascadeSpec, MarginGate
    qf = _qforest()
    X = _rows32(B=40)
    casc = CascadePredictor(qf, CascadeSpec((4, 12), MarginGate(np.inf)),
                            engine="bitvector", backend="pallas",
                            engine_kw=dict(block_b=32, block_t=4))
    got = casc.predict(X)
    full = pallas_qs_predictor(qf, block_b=32, block_t=4)
    np.testing.assert_array_equal(got, full.predict(X.astype(np.float64)))
    np.testing.assert_array_equal(got, full.predict(X))
    assert all(set(p._programs) == {False} for p in casc.stage_predictors)


def test_pallas_float_forest_transform_unchanged(class_forest):
    """A float forest has no grid to fold: its rows come out as float32,
    as ever, and it builds one program."""
    pred = pallas_qs_predictor(class_forest, block_b=32, block_t=4)
    X = np.random.default_rng(10).normal(size=(9, class_forest.n_features))
    for rows in (X, X.astype(np.float32)):
        assert not pred.folds_inputs(rows)
        got = pred.transform_inputs(rows)
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, rows.astype(np.float32))
        pred.predict(rows)
    assert set(pred._programs) == {False}
