"""The chip's peaks and the least time a scoring step can take.

``least_time`` counts the work any engine must do for a step, from the
forest's shape and dtype and the step's real rows alone, so it is the
same whoever implements the step.  It is a floor: the larger of

  * the bytes of the forest's least encoding read once (a threshold and
    a feature id per internal node, every leaf value), the step's rows
    at the forest's input width and its scores, over HBM bandwidth;
  * one compare per tree per row per internal node on the path to the
    tree's shallowest leaf, over the chip's peak rate for that dtype.

No engine can beat it, so a share of it never passes 100%.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# Google Cloud, "TPU v5e" (Cloud TPU documentation, system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s HBM2 per chip.
PEAKS = {
    "TPU v5 lite": {"float": 197e12, "int": 393e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Work:
    """What a forest costs per step, apart from its rows."""
    model_bytes: int          # least encoding of the forest
    row_bytes: int            # one input row plus its scores
    compares_per_row: int     # Σ over trees of the shallowest leaf's path
    integer: bool             # integer compares (quantized forest)


def work(n_nodes: np.ndarray, n_leaves: np.ndarray, shallowest: np.ndarray,
         n_features: int, n_classes: int, bits: int | None) -> Work:
    """``n_nodes``/``n_leaves``/``shallowest`` per tree (the last as
    depth with the root at 1); ``bits`` of a quantized forest's values,
    ``None`` for float32."""
    value = 4 if bits is None else -(-bits // 8)
    feature_id = max(1, -(-int(np.ceil(np.log2(max(n_features, 2)))) // 8))
    model = (int(n_nodes.sum()) * (value + feature_id)
             + int(n_leaves.sum()) * n_classes * value)
    row = n_features * value + n_classes * 4
    return Work(model, row, int((np.asarray(shallowest) - 1).sum()),
                bits is not None)


def least_time(w: Work, rows: int, peak: dict) -> tuple[float, str]:
    """Seconds of the least time of one step over ``rows`` real rows,
    and which term bounds it (``"bytes"`` or ``"compares"``)."""
    t_bytes = (w.model_bytes + rows * w.row_bytes) / peak["hbm_bytes_s"]
    t_ops = rows * w.compares_per_row / peak["int" if w.integer
                                             else "float"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "compares")
