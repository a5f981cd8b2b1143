"""jit'd wrappers around the Pallas forest kernels: host-side tile layout,
padding, dtype prep, predictor objects matching the XLA engines'
interface.

Tile layout (``quickscorer_kernel.py``): trees are padded to whole tiles
of ``block_t``; each tree gets ``Np`` node slots (real nodes, then the
bias node at slot ``N``, then inert padding) and ``Lp`` leaf rows, both
rounded up to the 8-row sublane tile.  Per tile, node tables are one
lane-dense row — node-major for QuickScorer (its AND runs over aligned
per-slot blocks), tree-major for bitmm and gemm (their per-tree matmuls
take aligned per-tree blocks).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine_select import bucket_batch
from ..core.forest import Forest
from ..core.quantize import (fold_bounds, input_cutoffs, leaf_scale,
                             quantize_inputs, select_columns)
from ..core.quickscorer import bitmm_full_word, bitmm_pack_arrays
from ..core.registry import BasePredictor, ensure_feature_column
from ..obs.retrace import fn_cache_size
from . import gemm_forest_kernel, quickscorer_kernel

SUBLANES, LANES = 8, 128
# scoped VMEM a kernel may use by default on v5e
VMEM_BUDGET = 16 * 2 ** 20


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _pad_to(x: np.ndarray, axis: int, mult: int, fill=0) -> np.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


def bucket_rows(n: int, block_b: int) -> int:
    """Padded batch size: ``block_b × 2^k`` — power-of-two buckets so any
    stream of batch sizes triggers at most O(log B_max) kernel compiles
    instead of one per distinct padded batch.  Same bucketing policy as
    the autotuner's ``engine_select.bucket_batch``, in units of blocks."""
    return block_b * bucket_batch(-(-n // block_b))


def _out_dtype(forest: Forest, block_t: int):
    """Kernel output dtype: int32 cross-tile accumulation for int-accum
    forests.  The per-tile partial stays an f32 leaf matmul, which is
    exact only while ``block_t × max|leaf| < 2^24`` — checked here at
    build time so the bit-exactness claim can never silently degrade
    (docs/QUANT.md)."""
    if not forest.int_accum:
        return jnp.float32
    lv = forest.leaf_value
    max_abs = int(np.abs(lv.astype(np.int64)).max()) if lv.size else 0
    if block_t * max_abs >= 2 ** 24:
        raise ValueError(
            f"pallas int accumulation needs block_t*max|leaf| < 2^24, got "
            f"{block_t}*{max_abs}; lower block_t or quantize to fewer bits")
    return jnp.int32


def _check_tiling(forest: Forest, block_b: int, block_t: int,
                  table_rows: int) -> None:
    """Refuse, at build time, a tiling the chip cannot compile.  On a TPU
    the batch block is the lane dimension of every transposed tile and
    QuickScorer's per-slot blocks are ``block_t`` sublanes, so
    ``block_b % 128 == 0`` and ``block_t % 8 == 0``; and one tile's
    working set must fit the scoped VMEM budget.  The CPU interpreter has
    neither bound."""
    if quickscorer_kernel.interpret_mode():
        return
    if block_b % LANES or block_t % SUBLANES:
        raise ValueError(
            f"pallas kernels on a TPU need block_b % {LANES} == 0 and "
            f"block_t % {SUBLANES} == 0, got block_b={block_b}, "
            f"block_t={block_t}")
    d = max(forest.n_features, 1)
    M = block_t * _round_up(forest.nodes_per_tree + 1, SUBLANES)
    K = block_t * _round_up(forest.n_leaves, SUBLANES)
    C = _round_up(forest.n_classes, LANES)
    words = (2 * block_b * d               # input block, double-buffered
             + d * M                       # one-hot feature select
             + 4 * block_b * M             # select, predicate, transposes
             + 2 * K * block_b             # exit-leaf one-hot
             + 2 * K * C                   # leaf table block
             + 2 * table_rows * M)         # node tables, per node slot
    if 4 * words > VMEM_BUDGET:
        raise ValueError(
            f"pallas tile working set ~{4 * words / 2 ** 20:.1f} MiB exceeds "
            f"the {VMEM_BUDGET / 2 ** 20:.0f} MiB scoped VMEM budget "
            f"(block_b={block_b}, block_t={block_t}, d={d}, "
            f"node slots per tile={M}); lower block_t or block_b")


def clamp_rows(X, lo, hi):
    """Rows into ``[lo, hi]`` per feature, NaN to ``lo``: the first op of
    a folded program.  An inf or NaN would make the kernel's one-hot
    feature select NaN for the whole row (``inf · 0``); the clamp keeps
    every value's grid value (``core.quantize.fold_bounds``)."""
    return jnp.where(jnp.isnan(X), lo, jnp.clip(X, lo, hi))


class _PallasPredictor(BasePredictor):
    """Kernel-backed predictor on the shared base: overrides the host
    path's bucketing/padding and descale steps, inherits the rest.

    ``kernel(X, thr)`` runs the engine on f32 rows against a node
    threshold table that ``layout(thresholds)`` lays out from (T, N)
    thresholds.  A quantized forest has two programs, each built on
    first use and chosen by the transformed rows' dtype: rows on its
    integer grid (``quantize_inputs``, e.g. float64 rows or a cascade's
    shared matrix) run against its integer thresholds; rows f32 holds
    exactly skip host quantization, are clamped (``clamp_rows``) and run
    against the folded cutoffs (``input_cutoffs``), which make the same
    decisions (docs/QUANT.md "Threshold folding").  Float forests have
    the one program."""

    def __init__(self, forest: Forest, kernel, layout, block_b: int,
                 tile: dict):
        if forest.flint:
            raise ValueError(
                "FLInt forests are unsupported on the pallas backend: the "
                "kernels cast input rows to f32, which cannot represent "
                "int32 FLInt keys (use backend='jax')")
        # no BasePredictor.__init__: the "compiled" state is the host
        # forest + the kernel's closure arrays; programs are built lazily
        self.forest = forest
        self.block_b = block_b
        self.tile = tile
        self.leaf_scale = leaf_scale(forest)
        self._kernel, self._layout = kernel, layout
        self._bounds = fold_bounds(forest)
        self._programs: dict[bool, object] = {}      # folded -> jitted fn
        self._lock = threading.Lock()
        self._variants: set = set()                  # (bucket, program)

    @property
    def _fn(self):
        """The program on the forest's own thresholds (the fused cascade
        traces it with quantized rows)."""
        return self._program_for(False)

    def _program_for(self, folded: bool):
        with self._lock:
            fn = self._programs.get(folded)
            if fn is None:
                fn = self._programs[folded] = self._build(folded)
        return fn

    def _build(self, folded: bool):
        kernel = self._kernel
        if not folded:
            thr = jnp.asarray(self._layout(self.forest.threshold))
            return jax.jit(lambda X: kernel(X, thr))
        thr = jnp.asarray(self._layout(input_cutoffs(self.forest)))
        lo, hi = (jnp.asarray(b) for b in self._bounds)
        return jax.jit(lambda X: kernel(clamp_rows(X, lo, hi), thr))

    def folds_inputs(self, X: np.ndarray) -> bool:
        return self._bounds is not None and np.can_cast(X.dtype, np.float32)

    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X)
        if self.folds_inputs(X):
            return np.asarray(select_columns(self.forest, X),
                              dtype=np.float32)
        Xq = quantize_inputs(self.forest, X)
        if np.issubdtype(self.forest.threshold.dtype, np.integer):
            return Xq                   # on the grid: integer dtype
        return Xq.astype(np.float32)

    def _program(self, Xq: np.ndarray):
        return self._program_for(
            self._bounds is not None
            and not np.issubdtype(Xq.dtype, np.integer))

    def _bucket(self, rows: int) -> int:
        return bucket_rows(rows, self.block_b)

    def _tile(self, Xq: np.ndarray, bucket: int) -> np.ndarray:
        # kernels take f32 rows; integer rows (the host path's grid, a
        # cascade's shared pre-quantized matrix) are cast here, exactly
        Xq = ensure_feature_column(np.asarray(Xq, dtype=np.float32))
        return _pad_to(Xq, 0, bucket)

    def _launch(self, fn, x):
        self._variants.add((x.shape[0], fn))
        return fn(x)

    def _untile(self, out: np.ndarray, rows: int) -> np.ndarray:
        # int-accum kernels return int32 totals; the f32 cast + pow2
        # descale matches the XLA engines' rounding bit-for-bit
        return out[:rows].astype(np.float32) / self.leaf_scale

    @property
    def n_compiles(self) -> int:
        """Distinct compiled kernel variants: each program's jit cache is
        keyed on the padded input shape, so distinct (bucket, program)
        pairs == distinct compiles."""
        return len(self._variants)

    def trace_cache_size(self):
        """Trace-cache entries over the programs built so far
        (``repro.obs.retrace``), ``None`` if one hides its cache."""
        sizes = [fn_cache_size(fn) for fn in list(self._programs.values())]
        return None if None in sizes else sum(sizes)


# --------------------------------------------------------------------------- #
# Host-side tile layout
# --------------------------------------------------------------------------- #
def _node_table(forest: Forest, block_t: int, bias_thr: float,
                thresholds=None):
    """(feat, thr) as (Tp, Np) per-tree slot tables: real nodes with
    ``thresholds`` (T, N) (default the forest's own), the bias node at
    slot N (feature -1 reads 0, so ``bias_thr`` decides whether it
    fires), inert padding (feature -1, threshold +inf).  Trees are
    padded to a multiple of ``block_t``."""
    T, N = forest.n_trees, forest.nodes_per_tree
    Tp, Np = _round_up(T, block_t), _round_up(N + 1, SUBLANES)
    if thresholds is None:
        thresholds = forest.threshold
    valid = forest.feature >= 0
    feat = np.full((Tp, Np), -1, np.int32)
    thr = np.full((Tp, Np), np.inf, np.float32)
    feat[:T, :N] = np.where(valid, forest.feature, -1)
    thr[:T, :N] = np.where(valid, thresholds.astype(np.float32),
                           np.float32(np.inf))
    thr[:, N] = bias_thr
    return feat, thr


def _thr_layout(forest: Forest, block_t: int, bias_thr: float,
                node_major: bool):
    """(T, N) thresholds → the kernel's threshold rows, as
    ``_node_table`` then ``_rows`` lay them out."""
    return lambda thresholds: _rows(
        _node_table(forest, block_t, bias_thr, thresholds)[1], block_t,
        node_major)


def _rows(a: np.ndarray, block_t: int, node_major: bool) -> np.ndarray:
    """(Tp, Np) slot table → (n_tiles, 1, block_t × Np) lane-dense rows,
    node-major (slot n of tree t at ``n·block_t + t``) or tree-major
    (``t·Np + n``)."""
    Tp, Np = a.shape
    a = a.reshape(Tp // block_t, block_t, Np)
    if node_major:
        a = a.transpose(0, 2, 1)
    return np.ascontiguousarray(a).reshape(Tp // block_t, 1, block_t * Np)


def _leaf_table(leaf_value: np.ndarray, block_t: int) -> np.ndarray:
    """(T, L, C) leaves → (n_tiles, block_t × Lp, C) f32, tree-major,
    zero rows for padding leaves and padding trees."""
    T, L, C = leaf_value.shape
    Tp, Lp = _round_up(T, block_t), _round_up(L, SUBLANES)
    lv = np.zeros((Tp, Lp, C), np.float32)
    lv[:T, :L] = leaf_value
    return lv.reshape(Tp // block_t, block_t * Lp, C)


def _qs_arrays(forest: Forest, block_t: int):
    """QuickScorer tile arrays (feat, thr, masks, leaf), node-major.  The
    bias node always fires (threshold -inf) and carries the initial
    leafidx as its mask; padding trees get a zero one → no exit leaf →
    leaf 0 → all-zero leaf row.  Shared by the per-forest predictor and
    the fused cascade builder, which preps each stage slice
    independently so stage scores match the staged per-stage kernels
    bit-for-bit."""
    T, N, W = forest.n_trees, forest.nodes_per_tree, forest.n_words
    feat, thr = _node_table(forest, block_t, -np.inf)
    Tp, Np = feat.shape
    masks = np.full((Tp, Np, W), 0xFFFFFFFF, np.uint32)
    masks[:T, :N] = forest.node_masks()
    masks[:, N] = _pad_to(forest.init_leafidx(), 0, block_t)      # pad: 0
    masks = masks.view(np.int32).reshape(Tp // block_t, block_t, Np, W)
    masks = np.ascontiguousarray(masks.transpose(0, 3, 2, 1)).reshape(
        Tp // block_t, W, Np * block_t)
    return (_rows(feat, block_t, True), _rows(thr, block_t, True), masks,
            _leaf_table(forest.leaf_value, block_t))


def _qs_table_rows(forest: Forest) -> int:
    """VMEM rows per node slot of the QuickScorer tables: feat and thr
    (one sublane tile each) plus the mask words."""
    return 2 * SUBLANES + _round_up(forest.n_words, SUBLANES)


def _device(*arrays):
    return tuple(jnp.asarray(a) for a in arrays)


def _tile(feat, leaf) -> dict:
    """A predictor's tile, from its node rows ``(tiles, 1, slots)`` and
    leaf table ``(tiles, rows, C)``: node slots and leaf rows per tree
    tile, and the tree tiles (``BasePredictor.launch_args``)."""
    return {"node_slots": int(feat.shape[-1]),
            "leaf_rows": int(leaf.shape[1]),
            "tree_tiles": int(feat.shape[0])}


def pallas_qs_predictor(forest: Forest, block_b: int = 128,
                        block_t: int = 8) -> _PallasPredictor:
    """QuickScorer bitvector engine, Pallas backend."""
    _check_tiling(forest, block_b, block_t, _qs_table_rows(forest))
    feat, _, masks, leaf = _qs_arrays(forest, block_t)
    feat, masks, leaf = _device(feat, masks, leaf)
    out_dtype = _out_dtype(forest, block_t)

    def kernel(X, thr):
        return quickscorer_kernel.qs_forward(
            X, feat, thr, masks, leaf, block_b=block_b, block_t=block_t,
            out_dtype=out_dtype)

    return _PallasPredictor(forest, kernel,
                            _thr_layout(forest, block_t, -np.inf, True),
                            block_b, _tile(feat, leaf))


def _cascade_arrays(forest: Forest, stages, block_t: int):
    """Stage-concatenated QuickScorer tile arrays plus the stages' tile
    offsets.  Each stage slice is laid out on its own (padded to whole
    tiles), so stage scores match the staged per-stage kernels
    bit-for-bit."""
    from ..cascade.predictor import tree_slice
    bounds = (0,) + tuple(stages)
    parts = [_qs_arrays(tree_slice(forest, bounds[k], bounds[k + 1]), block_t)
             for k in range(len(stages))]
    stage_tiles = (0,) + tuple(
        np.cumsum([p[0].shape[0] for p in parts]).tolist())
    return tuple(np.concatenate([p[i] for p in parts])
                 for i in range(4)) + (stage_tiles,)


def pallas_fused_cascade_qs(forest: Forest, stages, policy, *,
                            block_b: int = 128, block_t: int = 8):
    """Single-kernel cascade for the bitvector engine: all stages + the
    in-kernel gate (``cascade_kernel.py``).  Returns a jitted
    ``(Xp (B, d) f32, valid (B,) bool) -> (scores (B, C) descaled,
    exit_stage (B, 1) i32)`` with ``B`` a multiple of ``block_b``;
    ``FusedCascadePredictor`` owns the batch padding and exit-count
    reduction around it."""
    from . import cascade_kernel

    if forest.flint:
        raise ValueError(
            "FLInt forests are unsupported on the pallas backend: the "
            "fused cascade kernel casts input rows to f32, which cannot "
            "represent int32 FLInt keys (use backend='jax')")
    _check_tiling(forest, block_b, block_t, _qs_table_rows(forest))
    *arrays, stage_tiles = _cascade_arrays(forest, stages, block_t)
    feat, thr, masks, leaf = _device(*arrays)
    scale = leaf_scale(forest)

    @jax.jit
    def fn(Xp, valid):
        scores, exit_stage = cascade_kernel.cascade_qs_forward(
            Xp, valid.astype(jnp.float32)[:, None], feat, thr, masks, leaf,
            stage_tiles=stage_tiles, policy=policy, inv_scale=1.0 / scale,
            block_b=block_b, block_t=block_t)
        # power-of-two scale: the multiply is exact on quantized forests
        return scores * jnp.float32(1.0 / scale), exit_stage

    return fn


def _bitmm_arrays(forest: Forest, block_t: int):
    """Bit-matmul tile arrays (feat, thr, packed, leaf), tree-major, and
    the field layout (bits, npack).  The bias node always fires and adds
    the padding-leaf fields; padding trees get every field "cleared" →
    no survivor → leaf 0 → all-zero leaf row."""
    packed, bias, bits, npack = bitmm_pack_arrays(forest)
    T, N, G = packed.shape
    feat, thr = _node_table(forest, block_t, -np.inf)
    Tp, Np = feat.shape
    slots = np.zeros((Tp, Np, G), np.float32)
    slots[:T, :N] = packed
    slots[:, N] = _pad_to(bias, 0, block_t,
                          fill=float(bitmm_full_word(bits, npack)))
    slots = np.ascontiguousarray(
        slots.reshape(Tp // block_t, block_t, Np, G).transpose(0, 1, 3, 2))
    return (_rows(feat, block_t, False), _rows(thr, block_t, False), slots,
            _leaf_table(forest.leaf_value, block_t), bits, npack)


def pallas_bitmm_predictor(forest: Forest, block_b: int = 128,
                           block_t: int = 8) -> _PallasPredictor:
    """Bit-matmul QuickScorer engine, Pallas backend (DESIGN.md §2.4).

    Fuses cond-compute, the packed clear-count bit-matmul, exit-leaf
    recovery, and the leaf-table lookup in one VMEM-resident tile."""
    *arrays, bits, npack = _bitmm_arrays(forest, block_t)
    _check_tiling(forest, block_b, block_t,
                  2 * SUBLANES + _round_up(arrays[2].shape[2], SUBLANES))
    feat, _, packed, leaf = arrays
    feat, packed, leaf = _device(feat, packed, leaf)
    out_dtype = _out_dtype(forest, block_t)
    n_leaves = forest.n_leaves

    def kernel(X, thr):
        return quickscorer_kernel.qs_bitmm_forward(
            X, feat, thr, packed, leaf, bits=bits, npack=npack,
            n_leaves=n_leaves, block_b=block_b, block_t=block_t,
            out_dtype=out_dtype)

    return _PallasPredictor(forest, kernel,
                            _thr_layout(forest, block_t, -np.inf, False),
                            block_b, _tile(feat, leaf))


def _gemm_arrays(forest: Forest, block_t: int):
    """GEMM tile arrays (feat, thr, A, leaf), tree-major.  The bias node
    always goes left (threshold +inf) and its A column is ``-Bvec``, so a
    leaf is hit when its count lands on exactly 0; padding leaves and
    padding trees keep ``Bvec = L + 1`` and are never hit."""
    from ..core.baselines import compile_gemm
    g = compile_gemm(forest)                     # reuse A/Bvec construction
    T, N, L = forest.n_trees, forest.nodes_per_tree, forest.n_leaves
    feat, thr = _node_table(forest, block_t, np.inf)
    Tp, Np = feat.shape
    Lp = _round_up(L, SUBLANES)
    A = np.zeros((Tp, Np, Lp), np.float32)
    A[:T, :N, :L] = np.asarray(g.A)
    A[:, N, :] = -(L + 1.0)
    A[:T, N, :L] = -np.asarray(g.Bvec)
    A = np.ascontiguousarray(
        A.reshape(Tp // block_t, block_t, Np, Lp).transpose(0, 1, 3, 2))
    return (_rows(feat, block_t, False), _rows(thr, block_t, False), A,
            _leaf_table(np.asarray(g.leaf_val, dtype=np.float32), block_t))


def pallas_gemm_predictor(forest: Forest, block_b: int = 128,
                          block_t: int = 8) -> _PallasPredictor:
    """GEMM (Hummingbird/MXU) engine, Pallas backend."""
    _check_tiling(forest, block_b, block_t,
                  2 * SUBLANES + _round_up(forest.n_leaves, SUBLANES))
    feat, _, A, leaf = _gemm_arrays(forest, block_t)
    feat, A, leaf = _device(feat, A, leaf)
    out_dtype = _out_dtype(forest, block_t)

    def kernel(X, thr):
        return gemm_forest_kernel.gemm_forward(
            X, feat, thr, A, leaf, block_b=block_b, out_dtype=out_dtype)

    return _PallasPredictor(forest, kernel,
                            _thr_layout(forest, block_t, np.inf, False),
                            block_b, _tile(feat, leaf))
