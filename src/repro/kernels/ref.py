"""Pure-jnp oracles for the Pallas kernels (the `ref.py` layer).

These re-export the core engines' batch evaluators: the XLA engine IS the
mathematical reference; tests assert ``pallas ≈ ref ≈ numpy traversal
oracle`` across shape/dtype sweeps (on the CPU the Pallas kernels run in
the interpreter).
"""
from __future__ import annotations

import numpy as np

from ..core.baselines import compile_gemm, eval_gemm
from ..core.forest import Forest
from ..core.quantize import quantize_inputs
from ..core.quickscorer import compile_qs, eval_batch

import jax.numpy as jnp


def ref_qs(forest: Forest, X: np.ndarray) -> np.ndarray:
    """Bitvector-engine reference: (B, d) raw inputs → (B, C) scores."""
    qs = compile_qs(forest)
    Xq = quantize_inputs(forest, np.asarray(X))
    return np.asarray(eval_batch(qs, jnp.asarray(Xq)))


def ref_gemm(forest: Forest, X: np.ndarray) -> np.ndarray:
    g = compile_gemm(forest)
    Xq = quantize_inputs(forest, np.asarray(X))
    return np.asarray(eval_gemm(g, jnp.asarray(Xq)))


def ref_oracle(forest: Forest, X: np.ndarray) -> np.ndarray:
    """Slowest, most-trusted path: vectorized numpy root-to-leaf traversal."""
    from ..core.quantize import leaf_scale
    Xq = quantize_inputs(forest, np.asarray(X))
    return forest.predict_oracle(Xq) / leaf_scale(forest)
