"""Seeded forests and rows for the benchmark, vectorized in numpy.

A forest here is a plain ``Trees`` record of structure-of-arrays, the
benchmark's own: the reference (``reference.py``) traverses it, and
``to_program`` hands the same arrays to the system under test as its
Forest IR.  Node numbering follows the system's documented IR
(internal nodes in preorder, leaves in order, so a node over leaves
``[lo, hi)`` split at ``mid`` has its left child at ``node + 1`` and
its right child at ``node + (mid - lo)``), which lets every tree of the
ensemble be grown one level at a time for all trees at once.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Trees:
    """``T`` trees of at most ``L`` leaves over ``d`` features.

    ``feature``/``threshold``/``left``/``right``/``lo``/``mid``/``hi``
    are ``(T, L-1)``; a child code ``< 0`` is leaf ``-(code + 1)``;
    ``feature < 0`` marks a padding node.  ``leaf`` is ``(T, L, C)``.
    ``depth`` is the deepest leaf's depth, the root counting as 1, and
    ``shallowest`` each tree's shallowest leaf depth, ``(T,)``."""
    feature: np.ndarray
    threshold: np.ndarray
    left: np.ndarray
    right: np.ndarray
    lo: np.ndarray
    mid: np.ndarray
    hi: np.ndarray
    leaf: np.ndarray
    n_nodes: np.ndarray
    n_leaves: np.ndarray
    n_features: int
    depth: int
    shallowest: np.ndarray

    @property
    def shape(self) -> tuple:
        """``(T, L, C)``."""
        return self.leaf.shape


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def grow(rng: np.random.Generator, n_trees: int, n_leaves: int,
         n_features: int, n_classes: int, leaves: str) -> Trees:
    """Unbalanced trees of exactly ``n_leaves`` leaves.

    Each internal node over ``k`` leaves sends a uniform ``1..k-1`` of
    them left (random binary-search-tree shapes, as a forest grown
    without a depth limit has), tests a uniform feature against a
    standard-normal threshold, and ``leaves`` picks the leaf values:
    ``"votes"`` — class-vote fractions (Dirichlet(1), a random forest's
    averaged leaves), ``"normal"`` — N(0, 0.1²) scores (a boosted
    ranker's shrunk leaf outputs)."""
    T, L, d = n_trees, n_leaves, n_features
    if L < 2:
        raise ValueError(f"need at least 2 leaves per tree, got {L}")
    N = L - 1
    feature = np.full((T, N), -1, np.int32)
    threshold = np.zeros((T, N), np.float32)
    left = np.zeros((T, N), np.int32)
    right = np.zeros((T, N), np.int32)
    lo_a = np.zeros((T, N), np.int32)
    mid_a = np.zeros((T, N), np.int32)
    hi_a = np.zeros((T, N), np.int32)
    tree = np.arange(T)
    node = np.zeros(T, np.int64)
    lo = np.zeros(T, np.int64)
    hi = np.full(T, L, np.int64)
    level = 1
    shallowest = np.zeros(T, np.int32)
    while tree.size:
        n = tree.size
        mid = lo + 1 + (rng.random(n) * (hi - lo - 1)).astype(np.int64)
        feature[tree, node] = rng.integers(0, d, n)
        threshold[tree, node] = rng.standard_normal(n).astype(np.float32)
        left_leaf = mid - lo == 1
        right_leaf = hi - mid == 1
        left[tree, node] = np.where(left_leaf, -(lo + 1), node + 1)
        right[tree, node] = np.where(right_leaf, -(mid + 1),
                                     node + (mid - lo))
        lo_a[tree, node], mid_a[tree, node], hi_a[tree, node] = lo, mid, hi
        has_leaf = tree[left_leaf | right_leaf]
        shallowest[has_leaf[shallowest[has_leaf] == 0]] = level + 1
        go_l, go_r = ~left_leaf, ~right_leaf
        tree = np.concatenate([tree[go_l], tree[go_r]])
        lo, hi, node = (np.concatenate([lo[go_l], mid[go_r]]),
                        np.concatenate([mid[go_l], hi[go_r]]),
                        np.concatenate([node[go_l] + 1,
                                        (node + mid - lo)[go_r]]))
        level += 1
    if leaves == "votes":
        leaf = rng.dirichlet(np.ones(n_classes), size=(T, L))
    elif leaves == "normal":
        leaf = 0.1 * rng.standard_normal((T, L, n_classes))
    else:
        raise ValueError(f"unknown leaf kind {leaves!r}")
    return Trees(feature, threshold, left, right, lo_a, mid_a, hi_a,
                 leaf.astype(np.float32), np.full(T, N, np.int32),
                 np.full(T, L, np.int32), d, level, shallowest)


def rows(rng: np.random.Generator, n: int, d: int) -> np.ndarray:
    """``n`` float32 rows of standard-normal features (the thresholds'
    distribution, so every split sees both sides)."""
    return rng.standard_normal((n, d), dtype=np.float32)


def forest(cfg: dict) -> Trees:
    """The configuration's forest, from its own ``forest_seed``: the
    system compiles a forest's values into its programs, so the forest
    is fixed per configuration and only the traffic follows ``--seed``."""
    return grow(rng_for(cfg["forest_seed"], 0), cfg["n_trees"],
                cfg["n_leaves"], cfg["n_features"], cfg["n_classes"],
                cfg["leaves"])


def to_program(trees: Trees):
    """The system's Forest IR over the same arrays (copies)."""
    from repro.core.forest import Forest
    T, L, C = trees.shape
    return Forest(T, L, C, trees.n_features, trees.feature.copy(),
                  trees.threshold.copy(), trees.left.copy(),
                  trees.right.copy(), trees.lo.copy(), trees.mid.copy(),
                  trees.hi.copy(), trees.leaf.copy(), trees.n_nodes.copy(),
                  trees.n_leaves.copy(), max_depth=trees.depth)
