"""Baseline ensemble-traversal engines the paper compares against.

* ``native``   — per-level pointer-chasing traversal over child arrays (the
  paper's NATIVE/PRED baseline, Asadi et al. 2014): implemented as a
  ``fori_loop`` over tree depth with gathered node state.
* ``unrolled`` — the IF-ELSE analogue: identical math with the depth loop
  python-unrolled into straight-line HLO. On CPUs IF-ELSE wins via branch
  prediction; on TPU there are no branches, so this isolates the
  loop-vs-unroll HLO trade-off the paper's IE/NA gap degenerates to.
* ``gemm``     — Hummingbird-style tensor traversal (Nakandala et al. 2020)
  mapped onto the MXU; the paper dismisses this route for MCUs, on TPU it is
  the beyond-paper engine (DESIGN.md §2.3).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .forest import Forest
from .quantize import leaf_scale, quantize_inputs
from .quickscorer import acc_dtype_for, forest_acc_bits
from .registry import BasePredictor, register_engine


# --------------------------------------------------------------------------- #
# NATIVE / IF-ELSE: per-level traversal
# --------------------------------------------------------------------------- #
@dataclass
class CompiledNative:
    feat: jnp.ndarray       # (T, N) int32
    thr: jnp.ndarray        # (T, N)
    left: jnp.ndarray       # (T, N) int32 (<0 → leaf -(x+1))
    right: jnp.ndarray      # (T, N) int32
    leaf_val: jnp.ndarray   # (T, L, C)
    max_depth: int
    leaf_scale: float
    single_leaf: jnp.ndarray  # (T,) bool — degenerate single-leaf trees
    acc_bits: int = 32        # accumulator width (16 | 32)
    forest: Forest = None

    def transform_inputs(self, X):
        return quantize_inputs(self.forest, X) if self.forest is not None else X


def compile_native(forest: Forest) -> CompiledNative:
    return CompiledNative(
        feat=jnp.asarray(np.maximum(forest.feature, 0), dtype=jnp.int32),
        thr=jnp.asarray(forest.threshold),
        left=jnp.asarray(forest.left),
        right=jnp.asarray(forest.right),
        leaf_val=jnp.asarray(forest.leaf_value),
        max_depth=int(forest.max_depth),
        leaf_scale=leaf_scale(forest),
        single_leaf=jnp.asarray(forest.n_nodes == 0),
        acc_bits=forest_acc_bits(forest),
        forest=forest,
    )


def eval_native(nat: CompiledNative, X: jnp.ndarray,
                unroll: bool = False) -> jnp.ndarray:
    """X (B, d) → (B, C). State: current node per (instance, tree); negative
    codes are reached leaves (absorbing)."""
    B = X.shape[0]
    T, N = nat.feat.shape
    node0 = jnp.zeros((B, T), dtype=jnp.int32)

    def step(_, node):
        live = node >= 0
        idx = jnp.maximum(node, 0)
        f = jnp.take_along_axis(nat.feat[None], idx[..., None], axis=2)[..., 0]
        t = jnp.take_along_axis(nat.thr[None], idx[..., None], axis=2)[..., 0]
        x = jnp.take_along_axis(X[:, None, :], f[..., None], axis=2)[..., 0]
        l = jnp.take_along_axis(nat.left[None], idx[..., None], axis=2)[..., 0]
        r = jnp.take_along_axis(nat.right[None], idx[..., None], axis=2)[..., 0]
        nxt = jnp.where(x <= t, l, r)
        return jnp.where(live, nxt, node)

    if unroll:
        node = node0
        for i in range(nat.max_depth):
            node = step(i, node)
    else:
        node = jax.lax.fori_loop(0, nat.max_depth, step, node0)
    leaf = jnp.where(nat.single_leaf[None], 0, -node - 1)
    leaf = jnp.maximum(leaf, 0)                                   # safety
    vals = jnp.take_along_axis(
        nat.leaf_val[None], leaf[..., None, None], axis=2)[:, :, 0]
    acc = acc_dtype_for(nat.leaf_val.dtype, nat.acc_bits)
    score = vals.astype(acc).sum(axis=1, dtype=acc)
    return score.astype(jnp.float32) / nat.leaf_scale


# --------------------------------------------------------------------------- #
# GEMM (Hummingbird) engine
# --------------------------------------------------------------------------- #
@dataclass
class CompiledGEMM:
    feat: jnp.ndarray       # (T, N) int32
    thr: jnp.ndarray        # (T, N)
    valid: jnp.ndarray      # (T, N) bool
    A: jnp.ndarray          # (T, N, L)  +1 left-subtree, -1 right-subtree
    Bvec: jnp.ndarray       # (T, L)  required left-edge count (pad → +inf-ish)
    leaf_val: jnp.ndarray   # (T, L, C) f32 | i32 | i16
    leaf_scale: float
    compute_dtype: jnp.dtype
    acc_bits: int = 32      # accumulator width (16 | 32)
    forest: Forest = None

    def transform_inputs(self, X):
        return quantize_inputs(self.forest, X) if self.forest is not None else X


def compile_gemm(forest: Forest, compute_dtype=jnp.float32) -> CompiledGEMM:
    T, N = forest.feature.shape
    L = forest.n_leaves
    A = np.zeros((T, N, L), dtype=np.float32)
    Bvec = np.full((T, L), np.float32(L + 1))        # padding never matches
    for t in range(T):
        for n in range(int(forest.n_nodes[t])):
            lo, mid, hi = (int(forest.leaf_lo[t, n]), int(forest.leaf_mid[t, n]),
                           int(forest.leaf_hi[t, n]))
            A[t, n, lo:mid] += 1.0
            A[t, n, mid:hi] -= 1.0
        nl = int(forest.n_leaves_per_tree[t])
        Bvec[t, :nl] = A[t, :, :nl].clip(min=0).sum(axis=0)
    return CompiledGEMM(
        feat=jnp.asarray(np.maximum(forest.feature, 0), dtype=jnp.int32),
        thr=jnp.asarray(forest.threshold),
        valid=jnp.asarray(forest.feature >= 0),
        A=jnp.asarray(A, dtype=compute_dtype),
        Bvec=jnp.asarray(Bvec, dtype=compute_dtype),
        # integer leaves keep their dtype: the float leaf-einsum is exact
        # only below 2^24, the integer gather path in eval_gemm always is
        leaf_val=(jnp.asarray(forest.leaf_value)
                  if np.issubdtype(forest.leaf_value.dtype, np.integer)
                  else jnp.asarray(forest.leaf_value, dtype=jnp.float32)),
        leaf_scale=leaf_scale(forest),
        compute_dtype=compute_dtype,
        acc_bits=forest_acc_bits(forest),
        forest=forest,
    )


def eval_gemm(g: CompiledGEMM, X: jnp.ndarray) -> jnp.ndarray:
    """Two batched matmuls per tree block (MXU work):
       S (B,T,N) = 1{x <= t};  R = S @ A;  onehot = (R == Bvec);
       scores = Σ_t onehot @ leaf_val."""
    xf = X[:, g.feat]                                            # (B, T, N)
    S = ((xf <= g.thr[None]) & g.valid[None]).astype(g.compute_dtype)
    R = jnp.einsum("btn,tnl->btl", S, g.A)                       # MXU
    hit = R == g.Bvec[None]                                      # (B, T, L)
    if g.leaf_val.dtype == jnp.float32:
        # HIGHEST: a TPU's default precision rounds the f32 leaf values
        # to bf16 (CPU f32 is exact either way, so tests can't see it)
        score = jnp.einsum("btl,tlc->bc", hit.astype(jnp.float32),
                           g.leaf_val,
                           precision=jax.lax.Precision.HIGHEST)  # MXU
    else:
        # integer leaves: exactly one leaf per (row, tree) matches its
        # left-edge count, so argmax recovers the exit leaf; the gather-
        # sum stays in the integer accumulator (always exact, unlike a
        # float leaf-einsum above 2^24)
        leaf = jnp.argmax(hit, axis=2)                           # (B, T)
        vals = jnp.take_along_axis(
            g.leaf_val[None], leaf[..., None, None], axis=2)[:, :, 0]
        acc = acc_dtype_for(g.leaf_val.dtype, g.acc_bits)
        score = vals.astype(acc).sum(axis=1, dtype=acc)
    return score.astype(jnp.float32) / g.leaf_scale


def eval_unrolled(nat: CompiledNative, X: jnp.ndarray) -> jnp.ndarray:
    """``native`` with the depth loop python-unrolled (IF-ELSE analogue)."""
    return eval_native(nat, X, unroll=True)


class BaselinePredictor(BasePredictor):
    """Wrapper for the baseline engines (shared base: quantization + jit)."""


def native_predictor(forest: Forest, unroll=False) -> BaselinePredictor:
    nat = compile_native(forest)
    return BaselinePredictor(nat, eval_unrolled if unroll else eval_native)


def gemm_predictor(forest: Forest, compute_dtype=jnp.float32) -> BaselinePredictor:
    g = compile_gemm(forest, compute_dtype)
    return BaselinePredictor(g, eval_gemm)


_NATIVE_ARRAYS = ("feat", "thr", "left", "right", "leaf_val", "single_leaf")
register_engine(
    "native", tune_name="native", compile=compile_native,
    evaluate=eval_native, predictor_cls=BaselinePredictor, shardable=True,
    serial_arrays=_NATIVE_ARRAYS,
    doc="per-level pointer-chasing traversal (fori_loop over depth)")
register_engine(
    "unrolled", tune_name="unrolled", compile=compile_native,
    evaluate=eval_unrolled, predictor_cls=BaselinePredictor, shardable=True,
    serial_arrays=_NATIVE_ARRAYS,
    doc="native with the depth loop unrolled to straight-line HLO")
def _gemm_layout(forest: Forest, plan) -> str:
    dt = plan.engine_kw.get("compute_dtype")
    return (f"dense (T,N,L) traversal matrices, "
            f"dtype={getattr(dt, '__name__', dt) or 'f32'}")


register_engine(
    "gemm", tune_name="gemm", compile=compile_gemm, evaluate=eval_gemm,
    predictor_cls=BaselinePredictor, shardable=True, layout=_gemm_layout,
    serial_arrays=("feat", "thr", "valid", "A", "Bvec", "leaf_val"),
    doc="Hummingbird tensor traversal (two matmuls per tree block)")
