"""Pallas TPU kernel: fused cascade over QuickScorer bitvector stages.

One kernel evaluates *all* K cascade stages for a batch tile: stage tree
tiles run through the shared ``qs_tile_scores`` traversal, the gate's
pure-jax ``decide`` executes in-kernel on the descaled running scores,
and a per-row survivor mask lives in VMEM scratch.  Every tree tile (and
every gate) is wrapped in ``pl.when(any survivor)`` — a batch tile whose
rows are all decided skips the remaining stages' compute entirely, the
in-kernel analogue of the host loop's shrinking batch.

Versus the staged Pallas path this removes K-1 kernel launches, K-1
device→host score round-trips, and all survivor gather/re-pad work: the
input tile is read once, scores accumulate in the output block, and the
only things that ever reach the host are the final scores and a per-row
exit-stage vector (which the wrapper reduces to per-stage exit counts
in-graph).

Grid is ``(batch_tiles, tree_tiles)`` like the plain kernel: the tree
axis is sequential ("arbitrary"), so stages run in order within a batch
tile, and the forest streams through VMEM one ``block_t`` tile at a time
— the working set is one tile's, whatever the forest size.  Each stage is
padded to whole tiles with inert trees (exactly like the plain kernel's
padding), so a stage boundary is a static tile index where the gate runs
before that tile is scored, and scores match the staged per-stage
kernels bit-for-bit on quantized forests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quickscorer_kernel import pallas_call, qs_tile_scores


def _cascade_qs_kernel(x_ref, valid_ref, feat_ref, thr_ref, masks_ref,
                       leaf_ref, out_ref, exit_ref, active_ref, *,
                       stage_tiles, policy, inv_scale: float, block_t: int):
    """One (batch tile, tree tile) step of the whole cascade.

    x_ref      (Bt, d)       f32  — inputs (quantized forests: ints cast f32)
    valid_ref  (Bt, 1)       f32  — 1.0 for real rows, 0.0 for batch padding
    feat_ref … leaf_ref            — one tree tile (``qs_tile_scores``)
    out_ref    (Bt, C)       f32  — cumulative scores, raw leaf units
    exit_ref   (Bt, 1)       i32  — exit stage per row (default K-1)
    active_ref (Bt, 1)       f32  — VMEM scratch: the survivor mask

    ``stage_tiles`` are the static tile offsets of the stages (K+1
    entries); the gate of stage ``s`` runs at tile ``stage_tiles[s+1]``,
    before that tile is scored.  ``policy.decide`` runs on
    ``out * inv_scale`` (power-of-two scale → the multiply is exact on
    quantized forests, so the gate sees bit-identical scores to the
    staged host loop's).
    """
    j = pl.program_id(1)
    n_stages = len(stage_tiles) - 1

    @pl.when(j == 0)
    def _init():
        active_ref[...] = valid_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)
        exit_ref[...] = jnp.full(exit_ref.shape, n_stages - 1,
                                 dtype=jnp.int32)

    for s in range(n_stages - 1):
        @pl.when(j == stage_tiles[s + 1])
        def _boundary(s=s):
            @pl.when(jnp.any(active_ref[...] > 0))
            def _gate():
                keep = active_ref[...] > 0                    # (Bt, 1)
                ex = policy.decide(out_ref[...] * jnp.float32(inv_scale),
                                   s)[:, None] & keep
                exit_ref[...] = jnp.where(ex, s, exit_ref[...])
                active_ref[...] = jnp.where(ex, 0.0, active_ref[...])

    @pl.when(jnp.any(active_ref[...] > 0))
    def _score():
        part = qs_tile_scores(x_ref[...], feat_ref[...], thr_ref[...],
                              masks_ref[...], leaf_ref[...], block_t=block_t)
        out_ref[...] += jnp.where(active_ref[...] > 0, part, 0.0)


def cascade_qs_forward(x, valid, feat, thr, masks, leaf, *, stage_tiles,
                       policy, inv_scale: float, block_b: int, block_t: int):
    """Tiled arrays → ``(scores (B, C) raw units, exit_stage (B, 1))``.
    ``B`` must be a multiple of ``block_b`` (ops.py pads); the tree
    arrays stream one tile per grid step."""
    B, d = x.shape
    nT, _, M = feat.shape
    W = masks.shape[1]
    K, C = leaf.shape[1:]
    kernel = functools.partial(_cascade_qs_kernel,
                               stage_tiles=tuple(stage_tiles),
                               policy=policy, inv_scale=inv_scale,
                               block_t=block_t)
    return pallas_call(
        kernel,
        semantics=("parallel", "arbitrary"),
        grid=(B // block_b, nT),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, W, M), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, K, C), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, C), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.float32)],
    )(x, valid, feat, thr, masks, leaf)
