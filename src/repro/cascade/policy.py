"""Gating policies for staged (cascade) ensemble evaluation.

Daghero et al. ("Dynamic Decision Tree Ensembles for Energy-Efficient
Inference on IoT Edge Nodes", PAPERS.md) observe that most inputs are
decided by a small prefix of the ensemble: a confidence gate between
stages routes only the hard inputs to the rest of the forest.  This
module holds the gate side of the subsystem (docs/CASCADE.md):

  * ``GatePolicy`` — the pluggable interface: ``prepare(forest, stages)``
    precomputes whatever per-stage state the gate needs from the host IR,
    ``decide(scores, stage)`` is the **pure-jax** decision rule mapping
    the batch's *cumulative* stage scores to a boolean exit mask, and
    ``exits(scores, stage)`` is its numpy-facing wrapper.  The staged
    host loop and the fused in-graph cascade (``cascade/fused.py``) both
    run the *same* jitted ``decide``, so their per-stage exit counts are
    identical by construction.
  * ``MarginGate`` / ``ProbaGate`` — heuristic confidence gates for
    classification forests: exit when the normalized top-1/top-2 margin
    (or the top-1 probability) clears a threshold.  ``threshold=inf``
    never fires — the conformance suite's "gate disabled" case.
  * ``ScoreBoundGate`` — *sound* early exit via remaining-score bounds:
    per-tree leaf min/max of the not-yet-evaluated trees bound how much
    the score can still move; a row exits only when its decision provably
    cannot flip (at ``slack=0``, ``predict_class`` equals the full
    forest's — bit-exactly on quantized forests; on float forests up to
    the stage-split f32 summation rounding, which can flip genuine
    near-ties).  This is the GBM-shaped gate (remaining logit mass), but
    it is defined for any leaf semantics.
  * ``calibrate()`` — picks the cheapest policy from a candidate grid
    whose held-out accuracy stays within ``floor_pp`` percentage points
    of the full forest, simulated on cumulative stage scores so no
    predictor is rebuilt per threshold.

Policies carry only scalar config in their init fields (serialized into
packed cascade artifacts by ``io/packed.py``); everything ``prepare``
derives is rebuilt from the forest on load.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field, fields
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine_select import bucket_batch
from ..core.forest import Forest
from ..core.quantize import leaf_scale
from ..core.registry import votes_mode


def normalize_scores_jnp(scores: jnp.ndarray, votes: bool) -> jnp.ndarray:
    """Traceable twin of ``registry.normalize_scores`` in canonical f32:
    vote counts normalize by total mass (all-zero rows fall back to
    uniform), margins/logits go through softmax.  It tolerates partial
    sums — a vote prefix simply has less total mass — so gate confidence
    and served ``predict_proba`` use the same rule.  Callers guard
    C >= 2.  Every op lowers inside a Pallas kernel body, so the fused
    cascade kernel can evaluate gates in-kernel."""
    s = scores.astype(jnp.float32)
    if votes:
        v = jnp.maximum(s, 0.0)
        tot = jnp.sum(v, axis=1, keepdims=True)
        uniform = jnp.float32(1.0 / s.shape[1])
        return jnp.where(tot > 0, v / jnp.where(tot > 0, tot, 1.0), uniform)
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=1, keepdims=True)


def _f32_down(x64: np.ndarray) -> np.ndarray:
    """f64 → f32 rounding toward -inf (exact values pass through)."""
    x32 = x64.astype(np.float32)
    hi = x32.astype(np.float64) > x64
    return np.where(hi, np.nextafter(x32, -np.inf), x32).astype(np.float32)


def _f32_up(x64: np.ndarray) -> np.ndarray:
    """f64 → f32 rounding toward +inf (exact values pass through)."""
    x32 = x64.astype(np.float32)
    lo = x32.astype(np.float64) < x64
    return np.where(lo, np.nextafter(x32, np.inf), x32).astype(np.float32)


def _argmax_onehot(s: jnp.ndarray) -> jnp.ndarray:
    """(n, C) → boolean one-hot of the *first* row maximum — matches
    ``np.argmax`` tie-breaking without ``argmax``/``one_hot``/``cumsum``
    ops, none of which lower inside a Mosaic kernel body: the first
    maximum is the smallest column index among the maxima."""
    C = s.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    eq = s == jnp.max(s, axis=1, keepdims=True)
    first = jnp.min(jnp.where(eq, col, C), axis=1, keepdims=True)
    return col == first


@dataclass
class GatePolicy:
    """Interface: subclasses implement ``decide`` (and usually ``prepare``).

    ``prepare(forest, stages)`` is called once per cascade build with the
    *host* forest and the normalized stage boundaries (cumulative tree
    counts, last == n_trees).  ``decide(scores, stage)`` is the pure-jax
    decision rule: cumulative descaled scores (n, C) f32 → boolean (n,)
    mask, True exits now.  It must be traceable (the fused cascade calls
    it inside one jitted program — for the bitvector Pallas path, inside
    the kernel body itself), with ``stage`` a static Python int.

    ``exits(scores, stage)`` is the numpy-facing wrapper the staged host
    loop calls between stages: it pads to the power-of-two batch bucket
    and runs the *same jitted* ``decide``, so staged and fused cascades
    make bit-identical gate decisions by construction.  Third-party
    policies may still override ``exits`` directly (numpy-only); such
    policies work with the staged ``CascadePredictor`` but cannot be
    fused."""

    def prepare(self, forest: Forest, stages: Sequence[int]) -> None:
        self._decide_jit = None

    def decide(self, scores: jnp.ndarray, stage: int) -> jnp.ndarray:
        raise NotImplementedError(
            f"{type(self).__name__} defines no pure-jax decide(); "
            "implement it (or override exits() and use the staged "
            "CascadePredictor — fused execution requires decide)")

    def exits(self, scores: np.ndarray, stage: int) -> np.ndarray:
        n = scores.shape[0]
        if n == 0:
            return np.zeros(0, dtype=bool)
        fn = getattr(self, "_decide_jit", None)
        if fn is None:
            # cache per prepared instance: decide closes over prepared
            # state, so prepare() resets the cache (set_policy copies
            # the policy before preparing — a stale trace never leaks)
            fn = self._decide_jit = jax.jit(self.decide,
                                            static_argnums=(1,))
        bucket = bucket_batch(n)
        s = np.zeros((bucket,) + scores.shape[1:], dtype=np.float32)
        s[:n] = scores
        return np.asarray(fn(jnp.asarray(s), stage))[:n]

    def tag(self) -> str:
        """Short candidate-name tag (autotuner cache: distinct configs
        must never alias — every init field participates)."""
        raise NotImplementedError


@dataclass
class MarginGate(GatePolicy):
    """Exit when the top-1 vs top-2 probability margin >= ``threshold``.

    ``threshold=inf`` never exits (gate disabled).  On C<2 forests
    (regression / ranking) no margin exists, so the gate never fires —
    use ``ScoreBoundGate`` there."""
    threshold: float = 0.9

    _votes: bool = field(default=True, init=False, repr=False, compare=False)
    _n_classes: int = field(default=1, init=False, repr=False, compare=False)

    def prepare(self, forest: Forest, stages: Sequence[int]) -> None:
        super().prepare(forest, stages)
        self._votes = votes_mode(forest)
        self._n_classes = forest.n_classes

    def decide(self, scores: jnp.ndarray, stage: int) -> jnp.ndarray:
        if self._n_classes < 2 or not np.isfinite(self.threshold):
            return jnp.zeros(scores.shape[0], dtype=bool)
        p = normalize_scores_jnp(scores, votes=self._votes)
        top = jnp.max(p, axis=1)
        second = jnp.max(jnp.where(_argmax_onehot(p), -jnp.inf, p), axis=1)
        return (top - second) >= jnp.float32(self.threshold)

    def tag(self) -> str:
        return f"margin{self.threshold:g}"


@dataclass
class ProbaGate(MarginGate):
    """Exit when the top-1 probability >= ``threshold``."""
    threshold: float = 0.95

    def decide(self, scores: jnp.ndarray, stage: int) -> jnp.ndarray:
        if self._n_classes < 2 or not np.isfinite(self.threshold):
            return jnp.zeros(scores.shape[0], dtype=bool)
        p = normalize_scores_jnp(scores, votes=self._votes)
        return jnp.max(p, axis=1) >= jnp.float32(self.threshold)

    def tag(self) -> str:
        return f"proba{self.threshold:g}"


@dataclass
class ScoreBoundGate(GatePolicy):
    """Sound early exit: remaining-score bounds from per-tree leaf
    min/max of the trees a row has not yet evaluated.

    After stage ``k`` a row's final score lies in
    ``[s + rest_min[k], s + rest_max[k]]`` componentwise.  A row exits
    when its decision provably cannot change:

      * C >= 2 — the current argmax class stays argmax even if every
        remaining tree votes worst-case against it;
      * C == 1 — the score's sign vs ``decision`` (GBM binary logit
        boundary, default 0) is already fixed.

    ``slack > 0`` relaxes soundness by that much score mass (exits
    earlier, may flip decisions by <= slack); ``slack = 0`` keeps
    ``predict_class`` equal to the full forest's — exactly so on
    quantized forests (integer stage sums); on float forests the
    cascade's stage-split f32 accumulation rounds differently from the
    base engine's single reduction, so a genuine near-tie (~1 ulp) can
    still resolve differently."""
    slack: float = 0.0
    decision: float = 0.0

    _rest_min: Optional[np.ndarray] = field(default=None, init=False,
                                            repr=False, compare=False)
    _rest_max: Optional[np.ndarray] = field(default=None, init=False,
                                            repr=False, compare=False)

    def prepare(self, forest: Forest, stages: Sequence[int]) -> None:
        super().prepare(forest, stages)
        raw = np.asarray(forest.leaf_value)
        scale = leaf_scale(forest)
        T, L, C = raw.shape
        real = np.arange(L)[None, :] < \
            np.asarray(forest.n_leaves_per_tree)[:, None]       # (T, L)
        bounds = [int(min(s, T)) for s in stages]
        if np.issubdtype(raw.dtype, np.integer):
            # quantized forests: exact integer gate arithmetic
            # (docs/QUANT.md).  Per-tree min/max and the suffix sums run
            # in int64 — no rounding anywhere — and the pow2 leaf-scale
            # descale is exact in f64.  When every bound is
            # f32-representable (always, in practice: |bound| < 2^24
            # scaled units) the cast is value-exact and no outward
            # rounding is applied — the gate bounds are bit-exact, the
            # soundness interval is tight.
            lv = raw.astype(np.int64)
            imin, imax = np.iinfo(np.int64).min, np.iinfo(np.int64).max
            tree_min = np.where(real[..., None], lv, imax).min(axis=1)
            tree_max = np.where(real[..., None], lv, imin).max(axis=1)
            zero = np.zeros((1, C), dtype=np.int64)
            suf_min = np.concatenate(
                [np.cumsum(tree_min[::-1], axis=0)[::-1], zero])
            suf_max = np.concatenate(
                [np.cumsum(tree_max[::-1], axis=0)[::-1], zero])
            rmin64 = np.stack([suf_min[b] for b in bounds]) / scale
            rmax64 = np.stack([suf_max[b] for b in bounds]) / scale
            rmin32 = rmin64.astype(np.float32)
            rmax32 = rmax64.astype(np.float32)
            if (np.all(rmin32.astype(np.float64) == rmin64)
                    and np.all(rmax32.astype(np.float64) == rmax64)):
                self._rest_min, self._rest_max = rmin32, rmax32
            else:        # bounds beyond f32's exact-integer range
                self._rest_min = _f32_down(rmin64)
                self._rest_max = _f32_up(rmax64)
            return
        lv = raw.astype(np.float64) / scale               # descaled, like scores
        tree_min = np.where(real[..., None], lv, np.inf).min(axis=1)   # (T, C)
        tree_max = np.where(real[..., None], lv, -np.inf).max(axis=1)
        # suffix sums: bounds over trees [stages[k], T) for each gate k
        suf_min = np.concatenate([np.cumsum(tree_min[::-1], axis=0)[::-1],
                                  np.zeros((1, C))])
        suf_max = np.concatenate([np.cumsum(tree_max[::-1], axis=0)[::-1],
                                  np.zeros((1, C))])
        # f32 (decide's canonical dtype), rounded *outward*: a
        # round-to-nearest cast could shrink an interval by 1 ulp and
        # make a "provably decided" row exit unsoundly on float forests
        self._rest_min = _f32_down(np.stack([suf_min[b] for b in bounds]))
        self._rest_max = _f32_up(np.stack([suf_max[b] for b in bounds]))

    def decide(self, scores: jnp.ndarray, stage: int) -> jnp.ndarray:
        s = scores.astype(jnp.float32)
        C = s.shape[1]
        # per-class bounds as python-float literals, not a constant array:
        # Pallas kernel bodies reject captured array constants, and the
        # f32 → float → f32 trip is value-exact
        lo = jnp.stack([s[:, c] + float(self._rest_min[stage][c])
                        for c in range(C)], axis=1)
        hi = jnp.stack([s[:, c] + float(self._rest_max[stage][c])
                        for c in range(C)], axis=1)
        if s.shape[1] < 2:
            return ((lo[:, 0] > self.decision - self.slack) |
                    (hi[:, 0] < self.decision + self.slack))
        onehot = _argmax_onehot(s)
        best_lo = jnp.sum(jnp.where(onehot, lo, 0.0), axis=1)
        other_hi = jnp.max(jnp.where(onehot, -jnp.inf, hi), axis=1)
        return best_lo > other_hi - jnp.float32(self.slack)

    def tag(self) -> str:
        t = "bound"
        if self.slack:
            t += f"{self.slack:g}"
        if self.decision:
            t += f"@d{self.decision:g}"
        return t


# --------------------------------------------------------------------------- #
# (De)serialization of policy config — packed cascade artifacts
# --------------------------------------------------------------------------- #
def policy_to_header(policy: GatePolicy) -> dict:
    """Policy → JSON-safe header dict: class path + init-field scalars.
    Derived (``prepare``) state is rebuilt from the forest on load.
    Non-finite floats (a disabled gate is ``MarginGate(inf)``) are
    encoded as tagged strings — ``json.dumps`` would otherwise emit the
    non-RFC-8259 literal ``Infinity`` into the packed header."""
    cfg = {}
    for f in fields(policy):
        if not f.init:
            continue
        v = getattr(policy, f.name)
        if not isinstance(v, (bool, int, float, str)) and v is not None:
            raise TypeError(f"policy field {f.name!r} of "
                            f"{type(policy).__name__} is not a scalar "
                            f"({type(v).__name__}) — cannot serialize")
        if isinstance(v, float) and not np.isfinite(v):
            v = {"__float__": repr(v)}          # 'inf' / '-inf' / 'nan'
        cfg[f.name] = v
    t = type(policy)
    return {"class": f"{t.__module__}:{t.__qualname__}", "config": cfg}


def policy_from_header(h: dict) -> GatePolicy:
    mod, attr = h["class"].split(":")
    cls = getattr(importlib.import_module(mod), attr)
    if not (isinstance(cls, type) and issubclass(cls, GatePolicy)):
        raise ValueError(f"{h['class']!r} is not a GatePolicy subclass")
    cfg = {k: float(v["__float__"])
           if isinstance(v, dict) and "__float__" in v else v
           for k, v in h.get("config", {}).items()}
    return cls(**cfg)


# --------------------------------------------------------------------------- #
# Gate simulation + threshold calibration
# --------------------------------------------------------------------------- #
def simulate_gate(policy: GatePolicy, cum_scores: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Replay the gate on precomputed cumulative stage scores.

    ``cum_scores`` is (K, B, C) — the score each row would have after
    stage k if it were still active (``CascadePredictor.cumulative_scores``).
    Returns ``(exit_stage (B,) int, final_scores (B, C))`` — exactly what
    a gated ``predict`` would produce, without re-running any engine.
    The policy must already be ``prepare``'d for these stages."""
    K, B, C = cum_scores.shape
    exit_stage = np.full(B, K - 1, dtype=np.int64)
    active = np.ones(B, dtype=bool)
    for k in range(K - 1):
        idx = np.nonzero(active)[0]
        if idx.size == 0:
            break
        ex = policy.exits(cum_scores[k, idx], k)
        exit_stage[idx[ex]] = k
        active[idx[ex]] = False
    final = cum_scores[exit_stage, np.arange(B)]
    return exit_stage, final


@dataclass
class CalibrationResult:
    policy: GatePolicy            # winner (prepared for the stages)
    accuracy: float               # held-out accuracy of the gated cascade
    full_accuracy: float          # held-out accuracy of the full forest
    mean_trees: float             # mean trees evaluated per row (gated)
    exit_fractions: list          # per-stage exit fraction under the winner
    table: list                   # one dict per candidate policy tried

    @property
    def accuracy_drop_pp(self) -> float:
        return (self.full_accuracy - self.accuracy) * 100.0


def default_policy_grid() -> list:
    return [MarginGate(t) for t in
            (0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 0.99)] + [ScoreBoundGate()]


def calibrate(pred, X_val: np.ndarray, y_val: np.ndarray, *,
              policies: Optional[Sequence[GatePolicy]] = None,
              floor_pp: float = 0.5) -> CalibrationResult:
    """Pick the cheapest gate whose held-out accuracy stays within
    ``floor_pp`` percentage points of the full forest.

    ``pred`` is a ``CascadePredictor`` (its stages are fixed; only the
    policy is swept).  Every candidate is simulated on one set of
    cumulative stage scores — no engine recompiles, no per-threshold
    predictions.  The contract: among candidates satisfying
    ``accuracy >= full_accuracy - floor_pp/100``, the one with the
    fewest mean trees evaluated wins; if none qualifies, the gate is
    disabled (``MarginGate(inf)`` — full forest, zero drop).  The
    returned policy is prepared; install it with ``pred.set_policy``."""
    y_val = np.asarray(y_val)
    cum = pred.cumulative_scores(X_val)                  # (K, B, C)
    stages = np.asarray(pred.stages, dtype=np.float64)
    full_cls = cum[-1].argmax(axis=1)
    full_acc = float((full_cls == y_val).mean())
    floor = full_acc - floor_pp / 100.0

    if policies is None:
        policies = default_policy_grid()
    candidates = list(policies) + [MarginGate(float("inf"))]  # safe fallback
    table = []
    best = None
    for pol in candidates:
        pol.prepare(pred.forest, pred.stages)
        exit_stage, final = simulate_gate(pol, cum)
        acc = float((final.argmax(axis=1) == y_val).mean())
        mean_trees = float(stages[exit_stage].mean())
        counts = np.bincount(exit_stage, minlength=len(pred.stages))
        row = {"policy": pol.tag(), "accuracy": acc,
               "mean_trees": mean_trees,
               "exit_fractions": (counts / max(len(y_val), 1)).tolist(),
               "ok": acc >= floor}
        table.append(row)
        if row["ok"] and (best is None
                          or mean_trees < best[0]
                          or (mean_trees == best[0] and acc > best[1])):
            best = (mean_trees, acc, pol, row)
    _, _, pol, row = best              # fallback always qualifies (acc==full)
    return CalibrationResult(policy=pol, accuracy=row["accuracy"],
                             full_accuracy=full_acc,
                             mean_trees=row["mean_trees"],
                             exit_fractions=row["exit_fractions"],
                             table=table)
