"""The benchmark's forest generator and plain reference."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import forestgen  # noqa: E402
import reference  # noqa: E402


def small(seed=3, T=40, L=16, d=7, C=3, leaves="votes"):
    return forestgen.grow(forestgen.rng_for(seed, 0), T, L, d, C, leaves)


@pytest.mark.parametrize("L", [2, 3, 16, 64])
def test_shapes_and_structure(L):
    tr = small(T=25, L=L)
    N = L - 1
    assert tr.feature.shape == tr.threshold.shape == (25, N)
    assert tr.leaf.shape == (25, L, 3)
    assert (tr.feature >= 0).all() and (tr.feature < 7).all()
    # each internal node splits its leaf interval; children agree
    assert ((tr.lo < tr.mid) & (tr.mid < tr.hi)).all()
    for t in range(25):
        leaves_seen = []
        for n in range(N):
            for code, lo, hi in ((tr.left[t, n], tr.lo[t, n], tr.mid[t, n]),
                                 (tr.right[t, n], tr.mid[t, n],
                                  tr.hi[t, n])):
                if code < 0:
                    assert hi - lo == 1 and -code - 1 == lo
                    leaves_seen.append(lo)
                else:
                    assert code > n          # preorder
                    assert (tr.lo[t, code], tr.hi[t, code]) == (lo, hi)
        assert sorted(leaves_seen) == list(range(L))


def depths(tr, t):
    """Leaf depths of tree ``t`` by walking it (root at depth 1)."""
    out, stack = [], [(0, 1)]
    while stack:
        n, dep = stack.pop()
        for c in (tr.left[t, n], tr.right[t, n]):
            if c < 0:
                out.append(dep + 1)
            else:
                stack.append((c, dep + 1))
    return out


def test_depth_and_shallowest_leaf():
    tr = small(T=60, L=32)
    per_tree = [depths(tr, t) for t in range(60)]
    assert tr.depth == max(max(d) for d in per_tree)
    assert tr.shallowest.tolist() == [min(d) for d in per_tree]


def test_determinism_by_seed():
    a, b, c = small(seed=5), small(seed=5), small(seed=6)
    for f in ("feature", "threshold", "left", "right", "leaf"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.threshold, c.threshold)
    r1 = forestgen.rows(forestgen.rng_for(2 ** 31 + 7, 1), 5, 4)
    r2 = forestgen.rows(forestgen.rng_for(2 ** 31 + 7, 1), 5, 4)
    np.testing.assert_array_equal(r1, r2)
    assert r1.dtype == np.float32


def test_leaf_kinds():
    votes = small(leaves="votes").leaf
    assert (votes >= 0).all()
    np.testing.assert_allclose(votes.sum(axis=2), 1.0, rtol=1e-5)
    assert small(leaves="normal", C=1).leaf.shape[2] == 1
    with pytest.raises(ValueError):
        small(leaves="bogus")


@pytest.mark.parametrize("leaves,C", [("votes", 3), ("normal", 1)])
def test_reference_matches_system_oracle(leaves, C):
    tr = small(T=30, L=16, d=7, C=C, leaves=leaves)
    X = forestgen.rows(forestgen.rng_for(3, 1), 300, 7)
    f = forestgen.to_program(tr)
    np.testing.assert_array_equal(reference.scores(tr, X),
                                  f.predict_oracle(X))


def test_quantized_reference_matches_system_oracle():
    from repro import core
    tr = small(T=30, L=16, d=7)
    X = forestgen.rows(forestgen.rng_for(3, 1), 300, 7)
    calib = forestgen.rows(forestgen.rng_for(3, 2), 500, 7)
    qf = core.quantize_forest(forestgen.to_program(tr), calib)
    g = reference.grid(tr, calib, 16)
    assert g.leaf_scale == qf.leaf_scale
    want = qf.predict_oracle(core.quantize_inputs(qf, X)) / qf.leaf_scale
    np.testing.assert_array_equal(reference.quantized_scores(tr, g, X),
                                  want)


def test_bf16_control_is_lower_precision():
    tr = small(T=200, L=16, d=7, C=1, leaves="normal")
    X = forestgen.rows(forestgen.rng_for(3, 1), 200, 7)
    exact = reference.scores(tr, X)
    low = reference.bf16_scores(tr, X)
    assert low.dtype == np.float32
    assert np.abs(low - exact).max() > 1e-3
