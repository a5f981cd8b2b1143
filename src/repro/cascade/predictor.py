"""Staged (cascade) ensemble evaluation over any registered engine.

The forest is partitioned into K tree-prefix stages; each stage's delta
sub-forest (trees ``[stages[k-1], stages[k])``) is compiled through the
ordinary engine pipeline, and between stages a ``GatePolicy`` decides
which rows exit early.  Surviving rows are gathered into a shrinking
batch, padded to the next power of two (``engine_select.bucket_batch``)
so every stage sees at most O(log B) distinct batch shapes — stage
retraces stay bounded exactly like the Pallas batch bucketing.

Exactness (docs/CASCADE.md): a row that reaches the last stage has
accumulated every tree's contribution, so with the gate disabled
(``MarginGate(inf)`` or a single stage) the cascade computes the same
function as the underlying engine — bit-exact on quantized forests
(integer partial sums, power-of-two leaf scale: the same argument as
tree-sharded execution, DESIGN.md §5).

``CascadePredictor`` satisfies the ``core.registry.Predictor`` protocol
(predict / predict_class / predict_proba / transform_inputs, plus
``host_forest``), serves through ``ForestServer`` (per-stage exit
fractions land in ``ServerStats``), and round-trips through packed
``.repro.npz`` artifacts (``io.save_predictor`` / ``io.load_predictor``,
kind="cascade") including the gate thresholds.
"""
from __future__ import annotations

import copy
import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from ..core import registry
from ..core.engine_select import bucket_batch
from ..core.forest import Forest
from ..core.quantize import quantize_inputs
from ..core.registry import normalize_scores
from .policy import GatePolicy, MarginGate


def default_policy() -> GatePolicy:
    return MarginGate(0.9)


@dataclass(frozen=True)
class CascadeSpec:
    """Declarative cascade request: stage boundaries (cumulative tree
    counts — ``(16, 48, 192)`` evaluates 16 trees, then 32 more, then
    144 more) plus the gate policy.  ``policy=None`` → ``MarginGate(0.9)``.
    ``fused=True`` lowers to ``FusedCascadePredictor`` (one jitted
    computation, zero host syncs between stages — docs/CASCADE.md).
    Passed to ``core.compile_forest(..., cascade=...)`` /
    ``compile_plan`` and swept by the autotuner via ``cascade_specs=``."""
    stages: tuple
    policy: Optional[GatePolicy] = None
    fused: bool = False

    def resolved_policy(self) -> GatePolicy:
        return self.policy if self.policy is not None else default_policy()

    def tag(self) -> str:
        """Autotuner candidate tag, e.g. ``cascade=16/48:margin0.9`` or
        ``cascade-fused=16/48:margin0.9``.  Every field that changes the
        compiled variant participates, so distinct cascades never alias
        in the timing cache — fused tags also key-miss any pre-fusion
        cache entries."""
        s = "/".join(str(int(x)) for x in self.stages)
        kind = "cascade-fused" if self.fused else "cascade"
        return f"{kind}={s}:{self.resolved_policy().tag()}"


def normalize_stages(stages: Sequence[int], n_trees: int) -> tuple:
    """Sorted unique positive boundaries, clamped to ``n_trees``; the
    final stage always covers the whole forest (appended if missing)."""
    out = sorted({min(int(s), n_trees) for s in stages})
    if any(s <= 0 for s in out):
        raise ValueError(f"stage boundaries must be positive, got {stages}")
    if not out or out[-1] != n_trees:
        out.append(n_trees)
    return tuple(out)


def tree_slice(forest: Forest, start: int, stop: int) -> Forest:
    """Sub-forest of trees ``[start, stop)`` — shares the ensemble-wide
    padding (L) and all quantization metadata, so per-stage engine
    outputs descale identically to the full forest's."""
    sl = slice(start, stop)
    return dataclasses.replace(
        forest, n_trees=stop - start,
        feature=forest.feature[sl], threshold=forest.threshold[sl],
        left=forest.left[sl], right=forest.right[sl],
        leaf_lo=forest.leaf_lo[sl], leaf_mid=forest.leaf_mid[sl],
        leaf_hi=forest.leaf_hi[sl], leaf_value=forest.leaf_value[sl],
        n_nodes=forest.n_nodes[sl],
        n_leaves_per_tree=forest.n_leaves_per_tree[sl])


class CascadePredictor:
    """Confidence-gated staged evaluation wrapping any registered engine.

    ``stage_predictors`` injects pre-built per-stage predictors (the
    packed-artifact load path); otherwise each stage's delta sub-forest
    is compiled through ``core.registry.build`` with the given
    engine/backend/engine_kw.
    """

    def __init__(self, forest: Forest, spec: CascadeSpec, *,
                 engine: str = "bitvector", backend: str = "jax",
                 engine_kw: Optional[dict] = None,
                 stage_predictors: Optional[list] = None):
        self.forest = forest
        self.engine = engine
        self.backend = backend
        self.engine_kw = dict(engine_kw or {})
        self.stages = normalize_stages(spec.stages, forest.n_trees)
        bounds = (0,) + self.stages
        if stage_predictors is not None:
            if len(stage_predictors) != len(self.stages):
                raise ValueError(
                    f"{len(stage_predictors)} stage predictors for "
                    f"{len(self.stages)} stages {self.stages}")
            self.stage_predictors = list(stage_predictors)
        else:
            build = registry.get(engine, backend).builder()
            self.stage_predictors = [
                build(tree_slice(forest, bounds[k], bounds[k + 1]),
                      **self.engine_kw)
                for k in range(len(self.stages))]
        # quantize once, not once per surviving stage: every stage slice
        # shares the full forest's quantization metadata, so stages that
        # expose predict_transformed can all eat one pre-transformed
        # matrix (third-party Predictors without it fall back to raw
        # rows + their own transform)
        self._pre_transform = all(
            hasattr(p, "predict_transformed") for p in self.stage_predictors)
        self.set_policy(spec.resolved_policy())
        self.reset_exit_stats()

    # ------------------------------------------------------------- policy
    def set_policy(self, policy: GatePolicy) -> None:
        """Install (a copy of) ``policy``, prepared for this cascade's
        forest and stages — e.g. the winner of ``policy.calibrate``."""
        self.policy = copy.copy(policy)
        self.policy.prepare(self.forest, self.stages)

    #: class-level flag — ``FusedCascadePredictor`` flips it; drives the
    #: spec/tag/describe/serialization split between the two variants
    fused = False

    @property
    def spec(self) -> CascadeSpec:
        return CascadeSpec(stages=self.stages, policy=self.policy,
                           fused=self.fused)

    def describe(self) -> str:
        s = "/".join(str(x) for x in self.stages)
        d = f"stages={s} policy={self.policy.tag()}"
        return f"fused {d}" if self.fused else d

    @property
    def host_syncs(self) -> int:
        """Device→host synchronizations per ``predict`` batch: the staged
        loop materializes every stage's scores on the host for the gate
        (one sync per stage); the fused predictor overrides this with 1."""
        return len(self.stages)

    def trace_cache_size(self) -> Optional[int]:
        """Total XLA trace-cache entries across the stage predictors —
        the retrace-detection surface (``repro.obs.retrace``): a growth
        after serving warmup means some stage saw a cold shape.  ``None``
        when no stage exposes a cache (monitoring degrades to no-op)."""
        from ..obs.retrace import jit_cache_size
        total, found = 0, False
        for p in self.stage_predictors:
            size = jit_cache_size(p)
            if size is not None:
                total, found = total + size, True
        return total if found else None

    # ------------------------------------------------------------ serving
    def reset_exit_stats(self) -> None:
        K = len(self.stages)
        self.last_exit_counts = np.zeros(K, dtype=np.int64)
        self.exit_counts = np.zeros(K, dtype=np.int64)

    @property
    def exit_fractions(self) -> np.ndarray:
        """Cumulative per-stage exit fractions over every ``predict``
        since the last ``reset_exit_stats`` (sums to 1 once any row ran)."""
        tot = int(self.exit_counts.sum())
        return self.exit_counts / max(tot, 1)

    @property
    def mean_trees_evaluated(self) -> float:
        """Mean trees evaluated per row under the cumulative exit counts
        (the cascade's work metric: full forest = ``n_trees``)."""
        tot = int(self.exit_counts.sum())
        if tot == 0:
            return float(self.forest.n_trees)
        return float((self.exit_counts * np.asarray(self.stages)).sum() / tot)

    # --------------------------------------------------------- prediction
    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        return quantize_inputs(self.forest, np.asarray(X))

    def host_forest(self) -> Forest:
        return self.forest

    def _stage_scores(self, k: int, X: np.ndarray) -> np.ndarray:
        """One stage's delta scores for the active rows, padded to the
        power-of-two bucket so stage recompiles stay bounded.  ``X`` is
        pre-transformed when ``_pre_transform`` is set, raw otherwise."""
        n = X.shape[0]
        bucket = bucket_batch(n)
        if bucket > n:
            # zero rows, not repeats of row 0: a pathological first row
            # would otherwise be re-evaluated up to bucket - n times per
            # stage; the padding is sliced off before any gate sees it
            X = np.concatenate(
                [X, np.zeros((bucket - n,) + X.shape[1:], dtype=X.dtype)])
        pred = self.stage_predictors[k]
        out = pred.predict_transformed(X) if self._pre_transform \
            else pred.predict(X)
        return out[:n]

    def predict(self, X: np.ndarray) -> np.ndarray:
        """(B, d) → (B, C) scores.  Rows that exit early return their
        cumulative prefix scores (partial vote/logit mass); rows that
        reach the last stage carry the exact full-forest score."""
        X = np.asarray(X)
        feed = self.transform_inputs(X) if self._pre_transform else X
        B = X.shape[0]
        K = len(self.stages)
        out = np.zeros((B, self.forest.n_classes), dtype=np.float32)
        counts = np.zeros(K, dtype=np.int64)
        active = np.arange(B)
        for k in range(K):
            if active.size == 0:
                break
            out[active] += self._stage_scores(k, feed[active])
            if k == K - 1:
                counts[k] += active.size
                break
            ex = self.policy.exits(out[active], k)
            counts[k] += int(ex.sum())
            active = active[~ex]
        self.last_exit_counts = counts
        self.exit_counts += counts
        return out

    def predict_class(self, X: np.ndarray) -> np.ndarray:
        return self.predict(X).argmax(axis=1)

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        # same votes-vs-logits rule as the gate's confidence normalization
        return normalize_scores(self.predict(X),
                                votes=registry.votes_mode(self.forest))

    def cumulative_scores(self, X: np.ndarray) -> np.ndarray:
        """(K, B, C) cumulative scores after each stage with the gate
        held open — every row through every stage.  The calibration
        input (``policy.calibrate`` / ``simulate_gate``); also the
        gate-disabled reference: ``cumulative_scores(X)[-1]`` equals the
        underlying engine's full-forest prediction."""
        X = np.asarray(X)
        feed = self.transform_inputs(X) if self._pre_transform else X
        acc = np.zeros((X.shape[0], self.forest.n_classes), dtype=np.float32)
        out = []
        for k in range(len(self.stages)):
            acc = acc + self._stage_scores(k, feed)
            out.append(acc)
        return np.stack(out)
