"""Pallas TPU kernel: GEMM (Hummingbird-style) forest traversal — the
beyond-paper MXU engine (DESIGN.md §2.3).

Per (batch, tree) tile, entirely in VMEM (tile layout: see
``quickscorer_kernel.py``):
    S      = 1{x[feat] <= thr}            one-hot matmul feature select
    R_t    = A_t @ S_t                    (Lp, Np) × (Np, Bt) per tree, MXU
    onehot = 1{R_t == 0}                  exit-leaf equality test
    out   += onehotᵀ @ leaf_val           (Tt·Lp, Bt)ᵀ × (Tt·Lp, C) MXU

Each tree's bias node always fires (threshold +inf) and its A column
holds ``-Bvec``, the required left-edge count, so the exit leaf is the
one whose count is exactly zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quickscorer_kernel import (HIGHEST, accumulate, leaf_scores,
                                 pallas_call, select_features)


def _gemm_kernel(x_ref, feat_ref, thr_ref, a_ref, leaf_ref, out_ref):
    """x (Bt, d) f32 | feat (1, M) i32 tree-major | thr (1, M) f32 |
    a (Tt, Lp, Np) f32 (±1 path entries, bias column -Bvec; padding
    leaves never reach 0) | leaf (Tt·Lp, C) f32 | out (Bt, C)."""
    Tt, Lp, Np = a_ref.shape
    S = (select_features(x_ref[...], feat_ref[...]) <= thr_ref[...]
         ).astype(jnp.float32).T                                  # (M, Bt)
    hits = []
    for t in range(Tt):
        # HIGHEST: -Bvec outgrows bf16's exact integers on deep trees
        R = jnp.dot(a_ref[t], S[t * Np:(t + 1) * Np], precision=HIGHEST,
                    preferred_element_type=jnp.float32)           # (Lp, Bt)
        hits.append(jnp.where(R == 0.0, 1.0, 0.0))
    lhot = jnp.concatenate(hits, axis=0)                          # (Tt·Lp, Bt)
    accumulate(out_ref, leaf_scores(leaf_ref[...], lhot))


def gemm_forward(x, feat, thr, A, leaf, *, block_b: int,
                 out_dtype=jnp.float32):
    """Tiled arrays (``ops.py``) → scores (B, C); ``B`` a multiple of
    ``block_b``."""
    B, d = x.shape
    nT, _, M = feat.shape
    Tt, Lp, Np = A.shape[1:]
    K, C = leaf.shape[1:]
    return pallas_call(
        _gemm_kernel,
        semantics=("parallel", "arbitrary"),
        grid=(B // block_b, nT),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, Tt, Lp, Np), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((None, K, C), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C), out_dtype),
    )(x, feat, thr, A, leaf)
