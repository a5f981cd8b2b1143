"""QuickScorer / V-QuickScorer on TPU-style lanes — the paper's core.

``CompiledQS`` holds the flat QuickScorer arrays (feature ids, thresholds,
interval bitmasks, leaf table). ``eval_batch`` is the pure-jnp reference
evaluation used as the kernel oracle AND as the XLA engine; the Pallas kernel
in ``repro.kernels.quickscorer_kernel`` computes the same function with
explicit VMEM tiling.

Semantics (paper Algorithm 1, adapted per DESIGN.md §2.1):

  * every node carries a bitmask that clears its *left-subtree* leaf interval;
  * the mask is applied iff ``x[feat] > thr`` (the instance goes right, so
    the left subtree becomes unreachable);
  * the exit leaf is the lowest surviving set bit (LSB-first convention);
  * the prediction is a leaf-table lookup summed over trees.

The per-feature sorted early-``break`` of the CPU algorithm is replaced by
full predication (all nodes evaluated, masked select) — lockstep VPU lanes
make data-dependent early exit counterproductive. A faithful scalar QS with
the sorted-feature early exit is kept in ``eval_scalar_numpy`` for oracle
cross-checks and CPU-semantics benchmarking.

``eval_batch_bitmm`` is the bit-matmul reformulation (DESIGN.md §2.4): the
predicated AND-reduction over the node axis is replaced by one batched
matmul ``cleared = cond @ clearbits`` so the dominant reduction runs on the
MXU (BLAS on CPU) instead of VPU AND-chains, and the ``(B, T, N, W)``
intermediate of ``mask_reduce`` is never materialised.  Per-leaf clear
*counts* are packed, several leaves per f32 mantissa lane, and the exit
leaf is recovered with the classic lowest-zero-field borrow trick — exact
for the *lowest* zero field, which is exactly QuickScorer's exit-leaf
semantics.  See ``compile_qs_bitmm`` for the layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .forest import Forest, WORD
from .quantize import accum_bits, leaf_scale, quantize_inputs
from .registry import BasePredictor, register_engine


def forest_acc_bits(forest: Forest) -> int:
    """Accumulator width an engine should compile for: 32 unless the
    forest opted into integer accumulation (``QuantSpec(int_accum=True)``)
    and its worst-case leaf sum provably fits int16 (``accum_bits`` — the
    compile-time no-overflow assertion, docs/QUANT.md)."""
    return accum_bits(forest) if forest.int_accum else 32


def acc_dtype_for(leaf_dtype, acc_bits: int):
    """Leaf storage dtype + compiled accumulator width → jnp accumulator
    dtype.  Float leaves always accumulate f32; integer leaves accumulate
    int32, narrowed to int16 only when the compile-time bound allows."""
    if leaf_dtype == jnp.float32:
        return jnp.float32
    return jnp.int16 if acc_bits == 16 else jnp.int32


@dataclass
class CompiledQS:
    """Flattened QuickScorer arrays (jnp, device-resident)."""
    feat: jnp.ndarray        # (T, N) int32, padding → 0
    thr: jnp.ndarray         # (T, N) f32 | i16 | i8
    valid: jnp.ndarray       # (T, N) bool
    masks: jnp.ndarray       # (T, N, W) uint32
    init_idx: jnp.ndarray    # (T, W) uint32
    leaf_val: jnp.ndarray    # (T, L, C) f32 | i32
    n_leaves: int
    n_classes: int
    n_features: int
    leaf_scale: float
    acc_bits: int = 32                # accumulator width (16 | 32)
    forest: Optional[Forest] = None   # host-side IR (for input quantization)

    @property
    def n_trees(self) -> int:
        return self.feat.shape[0]

    @property
    def n_words(self) -> int:
        return self.masks.shape[-1]

    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        return quantize_inputs(self.forest, X) if self.forest is not None else X


def compile_qs(forest: Forest) -> CompiledQS:
    masks = forest.node_masks()                       # (T, N, W) uint32
    valid = forest.feature >= 0
    return CompiledQS(
        feat=jnp.asarray(np.maximum(forest.feature, 0), dtype=jnp.int32),
        thr=jnp.asarray(forest.threshold),
        valid=jnp.asarray(valid),
        masks=jnp.asarray(masks),
        init_idx=jnp.asarray(forest.init_leafidx()),
        leaf_val=jnp.asarray(forest.leaf_value),
        n_leaves=forest.n_leaves,
        n_classes=forest.n_classes,
        n_features=forest.n_features,
        leaf_scale=leaf_scale(forest),
        acc_bits=forest_acc_bits(forest),
        forest=forest,
    )


# --------------------------------------------------------------------------- #
# Bit helpers (DESIGN.md §2.2)
# --------------------------------------------------------------------------- #
def ctz32(w: jnp.ndarray) -> jnp.ndarray:
    """Count-trailing-zeros of nonzero uint32: popcount((w & -w) - 1)."""
    w = w.astype(jnp.uint32)
    lsb = w & (jnp.uint32(0) - w)
    return jax.lax.population_count(lsb - jnp.uint32(1)).astype(jnp.int32)


def exit_leaf(leafidx: jnp.ndarray) -> jnp.ndarray:
    """leafidx (..., W) uint32 → lowest set bit index (...,) int32."""
    W = leafidx.shape[-1]
    nz = leafidx != 0
    first_w = jnp.argmax(nz, axis=-1)                           # (...,)
    w = jnp.take_along_axis(leafidx, first_w[..., None], axis=-1)[..., 0]
    return (first_w * WORD + ctz32(w)).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# Reference (pure-jnp) evaluation — also the XLA production engine
# --------------------------------------------------------------------------- #
def mask_reduce(cond: jnp.ndarray, masks: jnp.ndarray,
                init_idx: jnp.ndarray) -> jnp.ndarray:
    """cond (B, T, N) bool × masks (T, N, W) → leafidx (B, T, W).

    AND-reduction over the node axis with predication: nodes whose predicate
    is false contribute the identity mask (all ones)."""
    ones = jnp.uint32(0xFFFFFFFF)
    sel = jnp.where(cond[..., None], masks[None], ones)          # (B,T,N,W)
    red = jax.lax.reduce(sel, ones, jax.lax.bitwise_and, dimensions=(2,))
    return red & init_idx[None]


def eval_batch(qs: CompiledQS, X: jnp.ndarray) -> jnp.ndarray:
    """Full-batch QuickScorer: X (B, d) → scores (B, C). Pure jnp."""
    xf = X[:, qs.feat]                                          # (B, T, N)
    cond = (xf > qs.thr[None]) & qs.valid[None]
    leafidx = mask_reduce(cond, qs.masks, qs.init_idx)          # (B, T, W)
    leaf = exit_leaf(leafidx)                                   # (B, T)
    vals = jnp.take_along_axis(
        qs.leaf_val[None], leaf[..., None, None], axis=2)[:, :, 0]  # (B, T, C)
    acc_dtype = acc_dtype_for(qs.leaf_val.dtype, qs.acc_bits)
    # dtype= keeps the reduction itself in acc_dtype (sum would otherwise
    # widen int16 lanes back to int32 per numpy promotion rules)
    score = vals.astype(acc_dtype).sum(axis=1, dtype=acc_dtype)
    return score.astype(jnp.float32) / qs.leaf_scale


class QSPredictor(BasePredictor):
    """Bitvector-engine wrapper (shared base: quantization + jit cache)."""

    def __init__(self, qs: CompiledQS, eval_fn=None):
        super().__init__(qs, eval_fn or eval_batch)
        self.qs = qs


# --------------------------------------------------------------------------- #
# Bit-matmul QuickScorer (DESIGN.md §2.4) — MXU-resident mask reduction
# --------------------------------------------------------------------------- #
@dataclass
class CompiledBitMM:
    """Packed clear-count arrays for the bit-matmul engine.

    Layout: leaf ``l`` owns a ``bits``-wide field of packed word
    ``l // npack`` (field ``l % npack``, LSB-first).  ``packed[t, n, g]``
    holds node ``n``'s contribution to group ``g``: ``2^(bits*(l%npack))``
    summed over the leaves ``l`` of its clear interval ``[lo, mid)``.
    ``cond @ packed`` therefore accumulates, per leaf field, the number of
    firing ancestors that clear that leaf — exact in f32 because every
    packed word stays below 2^24.  ``bias`` marks padding leaves
    (``l >= n_leaves_per_tree``) as permanently cleared.
    """
    feat: jnp.ndarray        # (Tp, N) int32, padding → 0
    thr: jnp.ndarray         # (Tp, N) f32 | i16 | i8
    valid: jnp.ndarray       # (Tp, N) bool
    packed: jnp.ndarray      # (Tp, N, G) f32 packed clear-count weights
    bias: jnp.ndarray        # (Tp, G) f32 padding-leaf fields (always on)
    leaf_val: jnp.ndarray    # (Tp, L, C) f32 | i32
    bits: int                # field width (holds max clear count)
    npack: int               # leaves per packed word (bits * npack <= 24)
    n_leaves: int
    n_classes: int
    n_features: int
    n_trees: int             # real tree count (Tp >= n_trees is padded)
    tree_chunk: int          # scan tile size over the tree axis
    leaf_scale: float
    acc_bits: int = 32       # accumulator width (16 | 32)
    forest: Optional[Forest] = None

    @property
    def n_groups(self) -> int:
        return self.packed.shape[-1]

    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        return quantize_inputs(self.forest, X) if self.forest is not None else X


def bitmm_full_word(bits: int, npack: int) -> int:
    """Packed word with every field set to 1 — 'all leaves cleared'.  Used
    for padding-tree bias rows; as a uint32 it is also the borrow-trick
    low mask.  Single source of truth for the field layout."""
    return sum(1 << (bits * i) for i in range(npack))


def bitmm_field_layout(forest: Forest) -> tuple[int, int]:
    """Leaf-packing layout for the bit-matmul engine: (bits, npack).

    ``bits`` is sized from the forest's maximum per-leaf clear count (how
    many ancestors can clear one leaf), ``npack = 24 // bits`` leaves share
    one f32 word.  Exposed separately so the compiler's layout pass
    (``core/pipeline.py``) can record the decision."""
    T, L, N = forest.n_trees, forest.n_leaves, forest.nodes_per_tree
    valid = forest.feature >= 0
    lo = np.where(valid, forest.leaf_lo, 0)
    mid = np.where(valid, forest.leaf_mid, 0)
    # per-leaf clear counts via a difference array → field width
    diff = np.zeros((T, L + 1), dtype=np.int64)
    t_idx = np.repeat(np.arange(T), N)[valid.ravel()]
    np.add.at(diff, (t_idx, lo.ravel()[valid.ravel()]), 1)
    np.add.at(diff, (t_idx, mid.ravel()[valid.ravel()]), -1)
    counts = np.cumsum(diff[:, :L], axis=1)
    field_max = max(int(counts.max(initial=0)), 1)   # bias fields hold 1
    bits = max(int(np.ceil(np.log2(field_max + 1))), 1)
    npack = max(24 // bits, 1)
    return bits, npack


def bitmm_auto_chunk(n_trees: int, nodes_per_tree: int) -> int:
    """Default tree-tile size: ~16k nodes per scan tile."""
    return min(n_trees, max(1, 16384 // max(nodes_per_tree, 1)))


def bitmm_pack_arrays(forest: Forest):
    """Host-side packed clearbits: returns (packed (T,N,G) f32,
    bias (T,G) f32, bits, npack).  Shared by the XLA engine and the Pallas
    kernel wrapper."""
    T, L, N = forest.n_trees, forest.n_leaves, forest.nodes_per_tree
    valid = forest.feature >= 0
    lo = np.where(valid, forest.leaf_lo, 0)
    mid = np.where(valid, forest.leaf_mid, 0)
    bits, npack = bitmm_field_layout(forest)
    G = (L + npack - 1) // npack
    Lp = G * npack

    # packed interval weights via cumulative per-group weight table:
    # CW[l, g] = sum of 2^(bits*(l'%npack)) over l' < l with l'//npack == g,
    # so a node's row is CW[mid] - CW[lo].
    w = np.power(2.0, bits * (np.arange(Lp) % npack))
    gid = np.arange(Lp) // npack
    CW = np.zeros((Lp + 1, G))
    np.add.at(CW, (np.arange(Lp) + 1, gid), w)
    CW = np.cumsum(CW, axis=0)
    packed = (CW[mid] - CW[lo]) * valid[..., None]            # (T, N, G)
    bias = CW[Lp][None] - CW[forest.n_leaves_per_tree]        # (T, G)
    return packed.astype(np.float32), bias.astype(np.float32), bits, npack


def compile_qs_bitmm(forest: Forest,
                     tree_chunk: Optional[int] = None) -> CompiledBitMM:
    """Compile the bit-matmul engine.  ``tree_chunk`` bounds peak memory:
    evaluation scans over tiles of that many trees (auto: ~16k nodes per
    tile, so 1024-tree forests never materialise a full (B, T, ·) buffer)."""
    T, N = forest.n_trees, forest.nodes_per_tree
    packed, bias, bits, npack = bitmm_pack_arrays(forest)
    G = packed.shape[-1]
    if tree_chunk is None:
        tree_chunk = bitmm_auto_chunk(T, N)
    tree_chunk = max(1, min(tree_chunk, T))
    # rebalance so the last tile is nearly full (pad < n_chunks trees)
    n_chunks = -(-T // tree_chunk)
    tree_chunk = -(-T // n_chunks)
    pad = n_chunks * tree_chunk - T

    feat = np.maximum(forest.feature, 0).astype(np.int32)
    valid = forest.feature >= 0
    thr = forest.threshold
    leaf_val = forest.leaf_value
    if pad:
        # padding trees: no valid nodes, every leaf field biased "cleared"
        # → no survivor → leaf 0 → all-zero leaf row → contributes nothing.
        feat = np.concatenate([feat, np.zeros((pad, N), np.int32)])
        thr = np.concatenate([thr, np.zeros((pad, N), thr.dtype)])
        valid = np.concatenate([valid, np.zeros((pad, N), bool)])
        packed = np.concatenate([packed, np.zeros((pad, N, G), np.float32)])
        full = np.float32(bitmm_full_word(bits, npack))
        bias = np.concatenate([bias, np.full((pad, G), full, np.float32)])
        leaf_val = np.concatenate(
            [leaf_val, np.zeros((pad,) + leaf_val.shape[1:],
                                leaf_val.dtype)])
    return CompiledBitMM(
        feat=jnp.asarray(feat), thr=jnp.asarray(thr),
        valid=jnp.asarray(valid), packed=jnp.asarray(packed),
        bias=jnp.asarray(bias), leaf_val=jnp.asarray(leaf_val),
        bits=bits, npack=npack, n_leaves=forest.n_leaves,
        n_classes=forest.n_classes, n_features=forest.n_features,
        n_trees=T, tree_chunk=tree_chunk, leaf_scale=leaf_scale(forest),
        acc_bits=forest_acc_bits(forest), forest=forest,
    )


def bitmm_exit_leaf(words: jnp.ndarray, *, bits: int, npack: int,
                    n_leaves: int, axis: int = -1) -> jnp.ndarray:
    """Packed clear-count words f32 (groups G on ``axis``) → exit leaf
    int32, ``axis`` reduced to size 1.

    Lowest-zero-field borrow trick: ``(v - lo) & ~v & hi`` flags the high
    bit of every zero field; borrows only corrupt flags *above* the lowest
    genuine zero, so the least-significant set bit is always the true first
    surviving leaf of the word.  Words stay below 2^24, so int32 two's
    complement gives the same bits as unsigned arithmetic — and int32 is
    what Mosaic lowers (an f32 → uint32 cast is refused).  Pure jnp —
    shared by the XLA engine and the Pallas kernel.  Rows with no
    survivor (padding trees) map to 0."""
    axis = axis % words.ndim
    G = words.shape[axis]
    lo_mask = bitmm_full_word(bits, npack)
    hi_mask = bitmm_full_word(bits, npack) << (bits - 1)
    v = words.astype(jnp.int32)
    t = (v - lo_mask) & ~v & hi_mask
    fidx = jax.lax.population_count((t & -t) - 1) // bits
    giota = jax.lax.broadcasted_iota(jnp.int32, words.shape, axis)
    cand = jnp.where(t != 0, giota * npack + fidx, G * npack + 1)
    leaf = cand.min(axis=axis, keepdims=True)
    return jnp.where(leaf < n_leaves, leaf, 0)


def _bitmm_tile(bm: CompiledBitMM, X: jnp.ndarray, feat, thr, valid,
                packed, bias, lv, acc_dtype) -> jnp.ndarray:
    """Score one tile of trees: X (B, d) × tile arrays → (B, C) partial."""
    xf = X.T[feat]                                        # (Tc, N, B)
    cond = (xf > thr[..., None]) & valid[..., None]
    condT = jnp.transpose(cond, (0, 2, 1)).astype(jnp.float32)   # (Tc, B, N)
    # HIGHEST precision: packed words are exact integers up to 2^23 and a
    # TPU's default bf16 multiplies would truncate them (CPU f32 is exact
    # either way, so CI can't catch the downgrade).
    cleared = jax.lax.dot_general(
        condT, packed, (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # (Tc, B, G) MXU
    words = cleared + bias[:, None, :]
    leaf = bitmm_exit_leaf(words, bits=bm.bits, npack=bm.npack,
                           n_leaves=bm.n_leaves)[..., 0].T  # (B, Tc)
    vals = jnp.take_along_axis(
        lv[None], leaf[..., None, None], axis=2)[:, :, 0]  # (B, Tc, C)
    return vals.astype(acc_dtype).sum(axis=1, dtype=acc_dtype)


def eval_batch_bitmm(bm: CompiledBitMM, X: jnp.ndarray) -> jnp.ndarray:
    """Bit-matmul QuickScorer: X (B, d) → scores (B, C).

    Tree-chunked: a ``lax.scan`` over tiles of ``bm.tree_chunk`` trees keeps
    peak memory at O(B × tree_chunk × max(N, G)) regardless of forest size."""
    B = X.shape[0]
    Tp, N = bm.feat.shape
    G = bm.n_groups
    acc_dtype = acc_dtype_for(bm.leaf_val.dtype, bm.acc_bits)
    nc = Tp // bm.tree_chunk
    if nc <= 1:
        score = _bitmm_tile(bm, X, bm.feat, bm.thr, bm.valid, bm.packed,
                            bm.bias, bm.leaf_val, acc_dtype)
    else:
        Tc = bm.tree_chunk
        tiles = (bm.feat.reshape(nc, Tc, N), bm.thr.reshape(nc, Tc, N),
                 bm.valid.reshape(nc, Tc, N),
                 bm.packed.reshape(nc, Tc, N, G),
                 bm.bias.reshape(nc, Tc, G),
                 bm.leaf_val.reshape((nc, Tc) + bm.leaf_val.shape[1:]))

        def body(acc, tile):
            feat, thr, valid, packed, bias, lv = tile
            return acc + _bitmm_tile(bm, X, feat, thr, valid, packed,
                                     bias, lv, acc_dtype), None

        score, _ = jax.lax.scan(
            body, jnp.zeros((B, bm.n_classes), acc_dtype), tiles)
    return score.astype(jnp.float32) / bm.leaf_scale


class BitMMPredictor(BasePredictor):
    """Bit-matmul engine wrapper (shared base: quantization + jit cache)."""

    def __init__(self, bm: CompiledBitMM, eval_fn=None):
        super().__init__(bm, eval_fn or eval_batch_bitmm)
        self.bm = bm


# --------------------------------------------------------------------------- #
# Faithful scalar QuickScorer (paper Algorithm 1, with the sorted-threshold
# early exit) — numpy, used for oracle cross-checks and CPU-semantics bench.
# --------------------------------------------------------------------------- #
def build_feature_major(forest: Forest):
    """Feature-major node stream: for each feature, nodes sorted ascending by
    threshold — the order Algorithm 1 requires for its ``break``."""
    T, N = forest.feature.shape
    recs = []
    for t in range(T):
        for n in range(int(forest.n_nodes[t])):
            recs.append((int(forest.feature[t, n]),
                         float(forest.threshold[t, n]), t, n))
    recs.sort()
    feat = np.array([r[0] for r in recs], dtype=np.int32)
    thr = np.array([r[1] for r in recs], dtype=np.float64)
    tree = np.array([r[2] for r in recs], dtype=np.int32)
    node = np.array([r[3] for r in recs], dtype=np.int32)
    # feature segment boundaries
    starts = np.searchsorted(feat, np.arange(forest.n_features))
    ends = np.searchsorted(feat, np.arange(forest.n_features), side="right")
    return feat, thr, tree, node, starts, ends


def eval_scalar_numpy(forest: Forest, X: np.ndarray) -> np.ndarray:
    """Algorithm 1 verbatim (per instance, early break per feature)."""
    feat, thr, tree, node, starts, ends = build_feature_major(forest)
    masks = forest.node_masks()
    init = forest.init_leafidx()
    W = forest.n_words
    out = np.zeros((X.shape[0], forest.n_classes))
    lv = forest.leaf_value.astype(np.float64)
    for i, x in enumerate(X):
        leafidx = init.copy()
        for f in range(forest.n_features):
            for j in range(starts[f], ends[f]):
                if x[f] > thr[j]:
                    leafidx[tree[j]] &= masks[tree[j], node[j]]
                else:
                    break                      # thresholds ascending
        # exit leaf: lowest set bit
        for t in range(forest.n_trees):
            leaf = 0
            for w in range(W):
                v = int(leafidx[t, w])
                if v:
                    leaf = w * WORD + (v & -v).bit_length() - 1
                    break
            out[i] += lv[t, leaf]
    return out / leaf_scale(forest)


# --------------------------------------------------------------------------- #
# Registry entries (docs/DESIGN.md §4)
# --------------------------------------------------------------------------- #
def _bitmm_layout(forest: Forest, plan) -> str:
    """Pipeline layout hook: pick the leaf packing + tree tiling."""
    bits, npack = bitmm_field_layout(forest)
    if plan.n_devices > 1:
        # the tile size must divide the per-shard tree count — that is
        # _bitmm_shard_kw's call, made after the forest is device-padded
        return f"leaf-pack {bits}b×{npack}, tree_chunk=per-shard"
    plan.engine_kw.setdefault(
        "tree_chunk", bitmm_auto_chunk(forest.n_trees,
                                       forest.nodes_per_tree))
    return (f"leaf-pack {bits}b×{npack}, "
            f"tree_chunk={plan.engine_kw['tree_chunk']}")


def bitmm_pallas_layout(forest: Forest, plan) -> str:
    """Layout hook for the Pallas bitmm backend (tiling is block_* kw)."""
    bits, npack = bitmm_field_layout(forest)
    return f"leaf-pack {bits}b×{npack}, VMEM tiles"


def _bitmm_shard_kw(forest: Forest, n_shards: int) -> dict:
    """Tree-sharded bitmm needs a ``tree_chunk`` that divides the per-shard
    tree count, so every device reshapes its local tile stack the same way
    (the forest is already padded to a multiple of ``n_shards``)."""
    local = forest.n_trees // n_shards
    target = max(1, min(local, bitmm_auto_chunk(forest.n_trees,
                                                forest.nodes_per_tree)))
    chunk = max(d for d in range(1, target + 1) if local % d == 0)
    return {"tree_chunk": chunk}


register_engine(
    "bitvector", tune_name="qs", compile=compile_qs, evaluate=eval_batch,
    predictor_cls=QSPredictor, shardable=True,
    serial_arrays=("feat", "thr", "valid", "masks", "init_idx", "leaf_val"),
    doc="QuickScorer: predicated interval-mask AND-reduction over nodes")
register_engine(
    "bitmm", tune_name="qs-bitmm", compile=compile_qs_bitmm,
    evaluate=eval_batch_bitmm, predictor_cls=BitMMPredictor,
    shardable=True, shard_kw=_bitmm_shard_kw, layout=_bitmm_layout,
    serial_arrays=("feat", "thr", "valid", "packed", "bias", "leaf_val"),
    doc="bit-matmul QuickScorer: packed clear-count GEMM on the MXU")
