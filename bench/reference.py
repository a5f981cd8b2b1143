"""The plain reference: a numpy root-to-leaf traversal of ``Trees``.

It imports nothing of the system under test.  Its traversal follows the
oracle the system documents (``Forest.predict_oracle``): at each node
``x[feature] <= threshold`` goes left.  Quantized configurations are
quantized here too, by the paper's fixed-point scheme (arXiv 2305.08579
§5): per-feature min-max normalisation to ``[0, 1]`` from calibration
rows, ``floor(s·x)`` with ``s = 2^(bits-1)`` on inputs and thresholds,
and leaves scaled by the largest power of two ``≤ s`` that keeps every
leaf within the integer range.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import ml_dtypes
import numpy as np

from forestgen import Trees

BLOCK = 1 << 23          # (tree, row) pairs traversed per block


def leaves(trees: Trees, X: np.ndarray) -> np.ndarray:
    """``(R, T)`` leaf index each row reaches in each tree: every
    (row, tree) pair walks from the root, and pairs that reached a leaf
    drop out of the next level."""
    T, N = trees.feature.shape
    R, d = X.shape
    feat, thr = trees.feature.ravel(), trees.threshold.ravel()
    left, right = trees.left.ravel(), trees.right.ravel()
    out = np.empty(R * T, np.int32)
    step = max(1, BLOCK // T)
    for r0 in range(0, R, step):
        x = np.ascontiguousarray(X[r0:r0 + step]).ravel()
        pair = np.arange(r0 * T, min(r0 + step, R) * T)
        tree_at = (pair % T) * N
        row_at = (pair // T - r0) * d
        node = np.zeros(pair.size, np.int64)
        while pair.size:
            k = tree_at + node
            nxt = np.where(x[row_at + feat[k]] <= thr[k], left[k], right[k])
            done = nxt < 0
            out[pair[done]] = -nxt[done] - 1
            go = ~done
            pair, tree_at, row_at, node = (pair[go], tree_at[go],
                                           row_at[go], nxt[go])
    return out.reshape(R, T)


def scores(trees: Trees, X: np.ndarray) -> np.ndarray:
    """``(R, C)`` float64 sums of the reached leaves."""
    lf = leaves(trees, X)
    T = trees.feature.shape[0]
    vals = trees.leaf.astype(np.float64)[np.arange(T)[None, :], lf]
    return vals.sum(axis=1)


# --------------------------------------------------------------------------- #
# fixed point (paper §5)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class Grid:
    """The fixed-point grid of a quantized configuration."""
    lo: np.ndarray
    hi: np.ndarray
    scale: float
    leaf_scale: float
    bits: int

    @property
    def imax(self) -> int:
        return 2 ** (self.bits - 1) - 1

    def inputs(self, X: np.ndarray) -> np.ndarray:
        """Raw rows → integer grid (float64 holding whole numbers)."""
        Xn = np.clip((np.asarray(X, np.float64) - self.lo)
                     / (self.hi - self.lo), 0.0, 1.0)
        return np.clip(np.floor(self.scale * Xn), -self.imax - 1, self.imax)


def grid(trees: Trees, calib: np.ndarray, bits: int) -> Grid:
    c = np.asarray(calib, np.float64)
    lo, hi = c.min(axis=0), c.max(axis=0)
    hi = np.where(hi - lo <= 0, lo + 1.0, hi)
    s = float(2 ** (bits - 1))
    imax = 2 ** (bits - 1) - 1
    max_abs = float(np.abs(trees.leaf).max()) or 1.0
    s_leaf = s
    while s_leaf * max_abs > imax:
        s_leaf /= 2.0
    return Grid(lo, hi, s, s_leaf, bits)


def quantized(trees: Trees, g: Grid) -> Trees:
    """The same trees with thresholds and leaves on the grid ``g``
    (integers held in float64, so sums stay exact)."""
    f = np.maximum(trees.feature, 0)
    tn = np.clip((trees.threshold.astype(np.float64) - g.lo[f])
                 / (g.hi[f] - g.lo[f]), 0.0, 1.0)
    thr = np.clip(np.floor(g.scale * tn), -g.imax - 1, g.imax)
    leaf = np.clip(np.floor(g.leaf_scale * trees.leaf.astype(np.float64)),
                   -g.imax - 1, g.imax)
    return replace(trees, threshold=thr, leaf=leaf)


def quantized_scores(trees: Trees, g: Grid, X: np.ndarray) -> np.ndarray:
    """Scores of a quantized configuration in its own units: the integer
    leaf sums over ``leaf_scale``."""
    return scores(quantized(trees, g), g.inputs(X)) / g.leaf_scale


# --------------------------------------------------------------------------- #
# the control: one precision below the configuration's
# --------------------------------------------------------------------------- #
def bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16)


def bf16_scores(trees: Trees, X: np.ndarray) -> np.ndarray:
    """The float reference computed in bfloat16: rows, thresholds and
    leaves rounded to bfloat16, the sum kept in float32 — what a default-
    precision TPU matmul does to float32 operands."""
    low = replace(trees, threshold=bf16(trees.threshold).astype(np.float32),
                  leaf=bf16(trees.leaf).astype(np.float32))
    lf = leaves(low, bf16(X).astype(np.float32))
    T = trees.feature.shape[0]
    vals = low.leaf[np.arange(T)[None, :], lf]
    return vals.sum(axis=1, dtype=np.float32)
