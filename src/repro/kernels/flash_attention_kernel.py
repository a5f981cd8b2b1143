"""Pallas TPU kernel: GQA flash attention (the LM-side compute hot-spot).

Motivation (§Perf, smollm prefill_32k): the pure-JAX chunked flash in
models/attention.py materialises each (B, H, qc, kc) score/probability
block at an XLA fusion boundary — ~123 GB of HBM round-trips per layer at
S=32k. This kernel keeps the whole (block_q × block_k) tile plus the
online-softmax state (m, l, acc) in VMEM; HBM traffic collapses to the
linear q/k/v/out streams.

Layout: head-major (BH, S, hd) so the grid is
    (BH, nq, nk)   — "parallel", "parallel", "arbitrary"
with the kv axis innermost: the out block and the (m, l, acc) scratch are
revisited across `j` and live in VMEM for the whole row of kv blocks.

GQA: k/v stay at (B·K, S, hd); the q→kv head mapping happens in the
BlockSpec index_map (h // n_rep), so grouped-query heads never
materialise repeated K/V — same trick as the XLA engine (§Perf iter 4),
one level lower.

Causality is handled per tile: fully-masked tiles are skipped with
`pl.when` (their loads still happen; a production kernel would prune the
grid — noted in EXPERIMENTS.md), diagonal tiles apply an iota mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quickscorer_kernel import pallas_call

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, n_k: int, causal: bool,
                  scale: float):
    """One (bh, i, j) tile.

    q_ref (1, bq, hd); k_ref/v_ref (1, bk, hd); o_ref (1, bq, hd);
    scratch: m/l (bq,), acc (bq, hd) — persistent across the j axis."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: tile is live iff some q position ≥ some k position
    live = True
    if causal:
        live = (i + 1) * block_q - 1 >= j * block_k

    @pl.when(live if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_forward(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, block_q: int = 512,
                  block_k: int = 512, n_rep: int = 1) -> jnp.ndarray:
    """q (BH, Sq, hd); k/v (BK, Sk, hd) with BH = BK·n_rep (heads of one
    batch element contiguous). Returns (BH, Sq, hd)."""
    BH, Sq, hd = q.shape
    BK, Sk, _ = k.shape
    assert BH == BK * n_rep, (BH, BK, n_rep)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_k=nk,
        causal=causal, scale=scale)

    def q_map(b, i, j):
        return (b, i, 0)

    def kv_map(b, i, j):
        return (b // n_rep, j, 0)

    return pallas_call(
        kernel,
        semantics=("parallel", "parallel", "arbitrary"),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # m
            pltpu.VMEM((block_q,), jnp.float32),       # l
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
        ],
    )(q, k, v)


def flash_attention_bshd(q, k, v, *, causal=True, block_q=512, block_k=512):
    """Convenience wrapper over (B, S, H, hd) q and (B, S, K, hd) k/v —
    the models/attention.py layout."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    n_rep = H // K
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, k.shape[1], hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, v.shape[1], hd)
    out = flash_forward(qh, kh, vh, causal=causal, block_q=block_q,
                        block_k=block_k, n_rep=n_rep)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
