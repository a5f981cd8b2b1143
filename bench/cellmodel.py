"""A configuration made real: its forest, the system's form of it, and
the comparison of served scores with the plain reference."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

import forestgen
import reference
import roofline


@dataclass
class Model:
    cfg: dict
    trees: forestgen.Trees
    calib: Optional[np.ndarray]          # rows the fixed-point grid is set on
    grid: Optional[reference.Grid]
    work: roofline.Work

    @property
    def n_features(self) -> int:
        return self.cfg["n_features"]

    def program_forest(self, bits: Optional[int] = None):
        """The system's Forest IR, quantized through the system's own
        ``quantize_forest`` where the configuration is fixed point
        (``bits`` overrides the configuration's width)."""
        from repro import core
        f = forestgen.to_program(self.trees)
        bits = bits or (self.cfg.get("quant") or {}).get("bits")
        if bits is None:
            return f
        return core.quantize_forest(f, self.calib, core.QuantSpec(bits=bits))

    def reference(self, X: np.ndarray) -> np.ndarray:
        """``(R, C)`` reference scores, float64."""
        if self.grid is None:
            return reference.scores(self.trees, X)
        return reference.quantized_scores(self.trees, self.grid, X)

    def numbers(self, got: np.ndarray, want: np.ndarray) -> dict:
        """The compared numbers of served scores ``got`` against the
        reference ``want`` (same rows): ``gap_q``, the widest gap in
        leaf quanta, for a fixed-point configuration, whose integer sums
        are exact; ``score_err``, the widest absolute gap, for float."""
        gap = float(np.abs(np.asarray(got, np.float64) - want).max()) \
            if len(want) else 0.0
        if self.grid is None:
            return {"score_err": gap}
        return {"gap_q": gap * self.grid.leaf_scale}


def build(cfg: dict) -> Model:
    trees = forestgen.forest(cfg)
    quant = cfg.get("quant")
    calib = grid = None
    if quant:
        calib = forestgen.rows(forestgen.rng_for(cfg["forest_seed"], 1),
                               cfg["calibration_rows"], cfg["n_features"])
        grid = reference.grid(trees, calib, quant["bits"])
    work = roofline.work(trees.n_nodes, trees.n_leaves, trees.shallowest,
                         cfg["n_features"], cfg["n_classes"],
                         quant["bits"] if quant else None)
    return Model(cfg, trees, calib, grid, work)
