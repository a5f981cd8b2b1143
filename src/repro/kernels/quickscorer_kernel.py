"""Pallas TPU kernels: QuickScorer bitvector traversal (DESIGN.md §2) and
its bit-matmul variant (§2.4).

Grid ``(batch_tiles, tree_tiles)``; each program evaluates a
``(block_b × block_t)`` tile of (instances × trees) entirely in VMEM and
accumulates partial class scores into the output block, which is revisited
across the tree grid axis.

Tile layout.  ``ops.py`` lays every per-node array out on the host as one
lane-dense row per tree tile, so the kernel never reshapes lanes:

  * node tables are ``(1, M)`` rows with ``M = block_t × Np``: every tree
    owns ``Np`` node slots (its real nodes, one *bias node*, inert
    padding; ``Np`` a multiple of 8).  The bias node always fires and
    carries the tree's constant term — QuickScorer's initial leafidx, the
    bit-matmul's padding-leaf fields — so no per-tree column is needed.
  * feature select is one matmul ``X @ 1{iota_d == feat}`` (MXU) into a
    ``(block_b, M)`` tile; the predicate compares it against the
    threshold row; a 2-D transpose then puts the batch in the lanes, so
    every later step works on dense ``(·, block_b)`` vregs.  Bias and
    padding slots have feature -1 and read 0, so their threshold alone
    decides whether they fire.
  * leaf tables are ``(block_t × Lp, C)``; the exit-leaf one-hot
    ``(block_t × Lp, block_b)`` contracts against them in one MXU call
    that also sums over the tile's trees.

Quantized forests (int16/int8 thresholds) flow through the same kernels:
inputs/thresholds are exact small integers, compared in f32 (exact ≤ 2^24).

The interpreter runs only on the CPU backend (``interpret_mode``): on a
TPU every kernel compiles through Mosaic, never silently interprets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quickscorer import bitmm_exit_leaf

WORD = 32
HIGHEST = jax.lax.Precision.HIGHEST


def interpret_mode() -> bool:
    """True only on the CPU backend, where Pallas TPU kernels cannot
    compile and run in the interpreter.  Every forest kernel takes its
    mode from here, so a TPU never runs the interpreter."""
    return jax.default_backend() == "cpu"


def pallas_call(kernel, *, semantics: tuple, **kw):
    """``pl.pallas_call`` in the backend's mode: Mosaic with the grid's
    dimension semantics on a TPU, the interpreter on the CPU."""
    interpret = interpret_mode()
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=tuple(semantics))
    return pl.pallas_call(kernel, interpret=interpret,
                          compiler_params=params, **kw)


def _ctz(w: jnp.ndarray) -> jnp.ndarray:
    """Count trailing zeros of nonzero int32 words (two's complement:
    ``w & -w`` isolates the lowest set bit, also for bit 31)."""
    return jax.lax.population_count((w & -w) - 1)


def select_features(x, feat):
    """``x (Bt, d)`` gathered at node row ``feat (1, M)`` → ``(Bt, M)``
    f32; a slot with feature -1 (bias, padding) reads 0.  HIGHEST: the
    one-hot select must return x bit-exactly or near-threshold
    predicates flip under TPU bf16 multiplies."""
    d = x.shape[1]
    M = feat.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (d, M), 0)
              == feat).astype(jnp.float32)
    return jnp.dot(x.astype(jnp.float32), onehot, precision=HIGHEST,
                   preferred_element_type=jnp.float32)


def leaf_scores(leaf, lhot):
    """Exit-leaf one-hot ``(K, Bt)`` × leaf table ``(K, C)`` → ``(Bt, C)``
    (contracting K sums over the tile's trees too).  HIGHEST keeps f32
    leaf values intact; integer leaves stay exact below 2^24 (the
    builder asserts ``block_t × max|leaf| < 2^24``)."""
    return jax.lax.dot_general(lhot, leaf.astype(jnp.float32),
                               (((0,), (0,)), ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def leaf_onehot(leaf, n_leaves: int):
    """Per-tree exit leaves ``(Tt, Bt)`` int32 → stacked one-hot
    ``(Tt × n_leaves, Bt)`` f32, tree-major (the leaf table's row order)."""
    Tt, Bt = leaf.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_leaves, Bt), 0)
    return jnp.concatenate(
        [(iota == leaf[t:t + 1]).astype(jnp.float32) for t in range(Tt)],
        axis=0)


def qs_tile_scores(x, feat, thr, masks, leaf, *, block_t: int):
    """Score one (instances × trees) tile — the QuickScorer traversal
    shared by the plain kernel and the fused cascade kernel
    (``cascade_kernel.py``).  Operates on *values* read from refs.

    x      (Bt, d)        f32   — inputs (quantized forests: ints cast f32)
    feat   (1, M)         i32   — node features, node-major: slot n of
                                  tree t at ``n * block_t + t``
    thr    (1, M)         f32   — thresholds (padding +inf; bias -inf,
                                  so it always fires)
    masks  (W, M)         i32   — interval bitmasks (bias: initial leafidx)
    leaf   (Tt × Lp, C)   f32   — leaf table, tree-major (padding rows 0)
    returns (Bt, C)       f32   — tile partial scores (raw leaf units)
    """
    M = feat.shape[1]
    W = masks.shape[0]
    Lp = leaf.shape[0] // block_t
    n_slots = M // block_t
    cond = select_features(x, feat) > thr                         # (Bt, M)

    # ---- predicated mask AND over the node axis (VPU) -------------------- #
    # node-major rows: slot n of every tree is one aligned (Tt, Bt) block
    words = []
    for w in range(W):
        sel = jnp.where(cond, masks[w:w + 1], -1).T               # (M, Bt)
        acc = sel[0:block_t]
        for n in range(1, n_slots):
            acc = acc & sel[n * block_t:(n + 1) * block_t]
        words.append(acc)                                         # (Tt, Bt)

    # ---- exit leaf: first nonzero word, LSB isolate ----------------------- #
    leaf_idx = jnp.zeros_like(words[0])
    found = jnp.zeros(words[0].shape, dtype=jnp.bool_)
    for w, word in enumerate(words):
        hit = (word != 0) & ~found
        leaf_idx = jnp.where(hit, w * WORD + _ctz(word), leaf_idx)
        found = found | hit
    # padding trees: found stays False → leaf 0 → leaf row is zeros.
    return leaf_scores(leaf, leaf_onehot(leaf_idx, Lp))


def accumulate(out_ref, part):
    """Write the tile partial on the first tree tile, add it after.
    Integer ``out_ref``: the cross-tile running sum is int32, so totals
    stay exact for any tree count (docs/QUANT.md)."""
    part = part.astype(out_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = part

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += part


def _qs_kernel(x_ref, feat_ref, thr_ref, masks_ref, leaf_ref, out_ref, *,
               block_t: int):
    accumulate(out_ref, qs_tile_scores(
        x_ref[...], feat_ref[...], thr_ref[...], masks_ref[...],
        leaf_ref[...], block_t=block_t))


def qs_forward(x, feat, thr, masks, leaf, *, block_b: int, block_t: int,
               out_dtype=jnp.float32):
    """Tiled arrays (``ops.py``) → scores (B, C).  ``B`` must be a multiple
    of ``block_b``; the node/leaf arrays carry one leading entry per tree
    tile.  ``out_dtype=jnp.int32`` selects integer cross-tile
    accumulation for int-leaf forests."""
    B, d = x.shape
    nT, _, M = feat.shape
    W = masks.shape[1]
    K, C = leaf.shape[1:]
    return pallas_call(
        functools.partial(_qs_kernel, block_t=block_t),
        semantics=("parallel", "arbitrary"),
        grid=(B // block_b, nT),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, W, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, K, C), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C), out_dtype),
    )(x, feat, thr, masks, leaf)


# --------------------------------------------------------------------------- #
# Bit-matmul variant (DESIGN.md §2.4): the node-axis reduction is a per-tree
# MXU matmul against packed clear-count words instead of a VPU AND-chain.
# --------------------------------------------------------------------------- #
def _qs_bitmm_kernel(x_ref, feat_ref, thr_ref, packed_ref, leaf_ref,
                     out_ref, *, bits: int, npack: int, n_leaves: int,
                     block_t: int):
    """One (block_b, block_t) tile, fully VMEM-resident.

    x_ref      (Bt, d)          f32 — inputs (quantized: ints cast f32)
    feat_ref   (1, M)           i32 — node features, tree-major: slot n
                                      of tree t at ``t * Np + n``
    thr_ref    (1, M)           f32 — thresholds (padding +inf, bias -inf)
    packed_ref (Tt, G, Np)      f32 — packed clear-count weights per tree
                                      (bias slot: padding-leaf fields)
    leaf_ref   (Tt × Lp, C)     f32 — leaf table, tree-major
    out_ref    (Bt, C)               — accumulated over the tree grid axis

    Stages: one-hot feature select (MXU) → predicate → per-tree bit-matmul
    (MXU) → lowest-zero-field exit leaf (VPU bit tricks) → leaf one-hot ×
    leaf table (MXU).
    """
    Np = packed_ref.shape[-1]
    Lp = leaf_ref.shape[0] // block_t
    cond = select_features(x_ref[...], feat_ref[...]) > thr_ref[...]
    condT = cond.astype(jnp.float32).T                            # (M, Bt)
    leaves = []
    for t in range(block_t):
        # HIGHEST: packed words are exact integers up to 2^24; the TPU
        # default bf16 multiply would truncate their low fields.
        words = jnp.dot(packed_ref[t], condT[t * Np:(t + 1) * Np],
                        precision=HIGHEST,
                        preferred_element_type=jnp.float32)       # (G, Bt)
        # padding trees (bias all-on) have no survivor → leaf 0 → zero row
        leaves.append(bitmm_exit_leaf(words, bits=bits, npack=npack,
                                      n_leaves=n_leaves, axis=0))
    leaf_idx = jnp.concatenate(leaves, axis=0)                    # (Tt, Bt)
    accumulate(out_ref, leaf_scores(leaf_ref[...],
                                     leaf_onehot(leaf_idx, Lp)))


def qs_bitmm_forward(x, feat, thr, packed, leaf, *, bits: int, npack: int,
                     n_leaves: int, block_b: int, block_t: int,
                     out_dtype=jnp.float32):
    """Tiled arrays (``ops.py``) → scores (B, C).  ``B`` must be a multiple
    of ``block_b``.  ``out_dtype=jnp.int32`` selects integer cross-tile
    accumulation."""
    B, d = x.shape
    nT, _, M = feat.shape
    G, Np = packed.shape[-2:]
    K, C = leaf.shape[1:]
    kernel = functools.partial(_qs_bitmm_kernel, bits=bits, npack=npack,
                               n_leaves=n_leaves, block_t=block_t)
    return pallas_call(
        kernel,
        semantics=("parallel", "arbitrary"),
        grid=(B // block_b, nT),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, block_t, G, Np), lambda i, j: (j, 0, 0, 0)),
            pl.BlockSpec((None, K, C), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C), out_dtype),
    )(x, feat, thr, packed, leaf)
