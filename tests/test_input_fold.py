"""Threshold folding (docs/QUANT.md "Threshold folding"): a quantized
forest's input grid moved into its node thresholds.

For every node with threshold ``q_t``, ``x > input_cutoffs(f)[node]``
must decide exactly as ``quantize_inputs(x) > q_t`` for every float32
``x`` the folded program compares, and the clamp the program applies
first (``kernels.ops.clamp_rows``) must leave every value's grid value
as it was, ±inf and NaN included."""
import numpy as np
import pytest

from repro import core
from repro.core.quantize import (QuantSpec, flint_forest, flint_key,
                                 flint_value, fold_bounds, input_cutoffs,
                                 quantize_forest, quantize_inputs)
from repro.kernels.ops import clamp_rows

D = 12
F32 = np.finfo(np.float32)
SUB_MAX = np.nextafter(F32.smallest_normal, np.float32(0))


def _quantized(bits, grid):
    f = core.random_forest_ir(n_trees=40, n_leaves=16, n_features=D,
                              n_classes=3, seed=bits)
    X = np.random.default_rng(bits).normal(size=(2048, D)) \
        if grid == "calibration" else None
    return quantize_forest(f, X, QuantSpec(bits=bits))


def _cutoff_values(f, c):
    """Every finite cutoff and both of its float32 neighbours, as a
    column of every feature."""
    v = c[np.isfinite(c)]
    v = np.concatenate([v, np.nextafter(v, np.float32(-np.inf)),
                        np.nextafter(v, np.float32(np.inf))])
    return np.repeat(v[:, None], D, axis=1)


def _values(kind, f, c):
    """(n, D) float32 rows of one kind of value."""
    rng = np.random.default_rng(5)
    if kind == "random":
        return rng.normal(size=(4096, D)).astype(np.float32)
    if kind == "cutoffs":
        return _cutoff_values(f, c)
    if kind == "outside":
        span = f.feat_hi - f.feat_lo
        return np.concatenate([
            f.feat_lo - span * rng.uniform(0, 1e3, size=(256, D)),
            f.feat_hi + span * rng.uniform(0, 1e3, size=(256, D)),
            np.full((1, D), F32.max), np.full((1, D), -F32.max),
        ]).astype(np.float32)
    specials = {"zeros": [0.0, -0.0],
                "infinities": [np.inf, -np.inf],
                "subnormals": [F32.smallest_subnormal, -F32.smallest_subnormal,
                               SUB_MAX, -SUB_MAX, F32.smallest_normal,
                               -F32.smallest_normal]}[kind]
    return np.repeat(np.array(specials, np.float32)[:, None], D, axis=1)


KINDS = ["random", "cutoffs", "outside", "zeros", "infinities",
         "subnormals"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("grid", ["calibration", "thresholds"])
@pytest.mark.parametrize("bits", [16, 8])
def test_cutoff_decides_as_the_grid(bits, grid, kind):
    f = _quantized(bits, grid)
    c = input_cutoffs(f)
    X = _values(kind, f, c)
    valid = f.feature >= 0
    feat = np.where(valid, f.feature, 0)
    host = quantize_inputs(f, X)[:, feat] > f.threshold      # (n, T, N)
    folded = X[:, feat] > c
    np.testing.assert_array_equal(folded[:, valid], host[:, valid])


@pytest.mark.parametrize("kind", KINDS + ["nan"])
@pytest.mark.parametrize("grid", ["calibration", "thresholds"])
@pytest.mark.parametrize("bits", [16, 8])
def test_clamp_keeps_every_grid_value(bits, grid, kind):
    """The folded program's clamp changes no grid value, and after it
    every value is finite and decides as the host's grid does: NaN goes
    to the range's foot, grid value 0, as the host's cast of NaN gives."""
    f = _quantized(bits, grid)
    c = input_cutoffs(f)
    X = np.full((3, D), np.nan, np.float32) if kind == "nan" \
        else _values(kind, f, c)
    lo, hi = fold_bounds(f)
    Xc = np.asarray(clamp_rows(X, lo, hi))
    assert Xc.dtype == np.float32 and np.isfinite(Xc).all()
    np.testing.assert_array_equal(quantize_inputs(f, Xc),
                                  quantize_inputs(f, X))
    if kind == "nan":
        assert (quantize_inputs(f, Xc) == 0).all()
    valid = f.feature >= 0
    feat = np.where(valid, f.feature, 0)
    np.testing.assert_array_equal(
        (Xc[:, feat] > c)[:, valid],
        (quantize_inputs(f, X)[:, feat] > f.threshold)[:, valid])


@pytest.mark.parametrize("bits", [16, 8])
def test_cutoff_edges(bits):
    """Padding nodes never fire (+inf); a node at the grid's top never
    fires (+inf); a node below the grid's foot always fires (-inf); the
    cutoff is the last float32 on the node's side of the grid."""
    f = _quantized(bits, "calibration")
    imax = 2 ** (bits - 1) - 1
    f.threshold = f.threshold.copy()
    f.threshold[0, 0], f.threshold[1, 0] = imax, -1
    c = input_cutoffs(f)
    assert (c[f.feature < 0] == np.inf).all()
    assert c[0, 0] == np.inf and c[1, 0] == -np.inf
    ok = np.isfinite(c) & (f.feature >= 0)
    feat, thr, cut = f.feature[ok], f.threshold[ok], c[ok]
    col = np.zeros((len(cut), D), np.float32)
    at, above = col.copy(), col.copy()
    at[np.arange(len(cut)), feat] = cut
    above[np.arange(len(cut)), feat] = np.nextafter(cut,
                                                    np.float32(np.inf))
    rows = np.arange(len(cut))
    assert (quantize_inputs(f, at)[rows, feat] <= thr).all()
    assert (quantize_inputs(f, above)[rows, feat] > thr).all()


def test_flint_value_inverts_flint_key():
    v = np.array([-np.inf, -F32.max, -1.5, -F32.smallest_subnormal, -0.0,
                  0.0, F32.smallest_subnormal, 2.0, F32.max, np.inf],
                 np.float32)
    back = flint_value(flint_key(v))
    np.testing.assert_array_equal(back.view(np.int32), v.view(np.int32))


def _bounds_of(kind):
    f = core.random_forest_ir(n_trees=8, n_leaves=8, n_features=4,
                              n_classes=2, seed=3)
    X = np.random.default_rng(3).normal(size=(256, 4))
    if kind == "float":
        return fold_bounds(f)
    if kind == "flint":
        return fold_bounds(flint_forest(f))
    if kind == "leaves_only":
        return fold_bounds(quantize_forest(
            f, X, QuantSpec(quantize_splits=False)))
    if kind == "huge_range":
        return fold_bounds(quantize_forest(f, X * 1e39))
    if kind == "subnormal_grid":
        return fold_bounds(quantize_forest(f, X * 1e-40))
    return fold_bounds(quantize_forest(f, X))


@pytest.mark.parametrize("kind", ["float", "flint", "leaves_only",
                                  "huge_range", "subnormal_grid"])
def test_forests_that_do_not_fold(kind):
    assert _bounds_of(kind) is None


def test_bounds_enclose_the_range():
    f = _quantized(16, "calibration")
    lo, hi = fold_bounds(f)
    assert lo.dtype == hi.dtype == np.float32
    assert (lo <= f.feat_lo).all() and (hi >= f.feat_hi).all()
    assert (np.nextafter(lo, np.float32(np.inf)) > f.feat_lo).all()
    assert (np.nextafter(hi, np.float32(-np.inf)) < f.feat_hi).all()
