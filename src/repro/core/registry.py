"""Unified engine registry — every traversal engine registers exactly once.

The paper's conclusion (the fastest engine depends on forest shape and
device) only pays off if engines are interchangeable.  This module is the
single source of truth that makes them so:

  * ``EngineSpec`` — one record per (engine, backend): how to compile the
    Forest IR into device arrays, how to evaluate them, how to wrap the
    result into a predictor, and whether the engine supports tree-sharded
    execution (``core/shard.py``).
  * ``register_engine(...)`` — decorator/registration call used by the
    engine modules (``quickscorer``, ``rapidscorer``, ``baselines``) and,
    via deferred targets, the Pallas kernels in ``kernels/ops.py``.
  * ``BasePredictor`` — the shared predictor base (input quantization,
    jit cache, ``predict`` / ``predict_class`` / ``predict_proba``) that
    replaces the per-engine ``XPredictor`` copies.

``core.compile_forest``, the autotuner (``core/engine_select.py``), the
pass pipeline (``core/pipeline.py``), benchmarks, and the agreement test
suite all resolve engines through this table — there is no second
engine-name list anywhere in the tree (see docs/DESIGN.md §4).
"""
from __future__ import annotations

import functools
import importlib
from dataclasses import dataclass, field, replace
from typing import Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _obs_metrics
from ..obs.trace import phase


# --------------------------------------------------------------------------- #
# Protocols
# --------------------------------------------------------------------------- #
@runtime_checkable
class Predictor(Protocol):
    """What every engine hands back to the user/serving layer."""

    def transform_inputs(self, X: np.ndarray) -> np.ndarray: ...
    def predict(self, X: np.ndarray) -> np.ndarray: ...
    def predict_class(self, X: np.ndarray) -> np.ndarray: ...


@runtime_checkable
class ForestEngine(Protocol):
    """A registered engine: ``compile(forest, **kw) → Predictor``.

    ``EngineSpec`` satisfies this via ``builder()`` — note that the
    spec's ``compile`` *field* is the lower-level array compiler
    (``forest → compiled``), wrapped by ``predictor_cls`` to produce the
    Predictor; register either that pair or a builder, never a callable
    that already returns a Predictor as ``compile=``."""

    def compile(self, forest, **kw) -> Predictor: ...


# --------------------------------------------------------------------------- #
# Shared predictor base
# --------------------------------------------------------------------------- #
def normalize_scores(scores: np.ndarray,
                     votes: Optional[bool] = None) -> np.ndarray:
    """(B, C) raw class scores → per-row probabilities (paper §4).

    ``votes=True`` — non-negative vote mass (averaged RF leaves): rows
    divide by their sum (all-zero rows fall back to uniform).
    ``votes=False`` — logit leaves (boosting): softmax.
    ``votes=None`` infers from the scores at hand — predictors instead
    pass the mode derived from the forest's leaf table, so one input row
    always gets the same probabilities regardless of its batchmates.
    """
    s = np.asarray(scores, dtype=np.float64)
    if s.ndim != 2 or s.shape[1] < 2:
        raise ValueError(
            f"predict_proba needs a classification forest (C >= 2 class "
            f"scores); got shape {s.shape}")
    if votes is None:
        votes = bool((s >= 0).all())
    if votes:
        s = np.maximum(s, 0.0)         # guard: quantization can dip below 0
        tot = s.sum(axis=1, keepdims=True)
        uniform = np.full_like(s, 1.0 / s.shape[1])
        return np.where(tot > 0, s / np.where(tot > 0, tot, 1.0), uniform)
    z = np.exp(s - s.max(axis=1, keepdims=True))
    return z / z.sum(axis=1, keepdims=True)


def votes_mode(forest) -> bool:
    """Whether a forest's class scores are vote mass (RF averaging, all
    leaves >= 0 → sum-normalize) or logits (boosting → softmax).  The
    single source of this inference: ``predict_proba`` here and the
    cascade gate confidences (``repro.cascade.policy``) both use it, so
    served probabilities and gate decisions can never normalize
    differently."""
    return bool((np.asarray(forest.leaf_value) >= 0).all())


def ensure_feature_column(X: np.ndarray) -> np.ndarray:
    """0-feature ensembles (every tree a single leaf) hand engines a
    (B, 0) input, but all engines gather feature column 0 unconditionally
    (padding nodes are masked by ``valid``, never skipped) — give them
    one dummy column instead of an empty gather axis."""
    if X.ndim == 2 and X.shape[1] == 0:
        return np.zeros((X.shape[0], 1), dtype=X.dtype)
    return X


class BasePredictor:
    """Shared engine wrapper: input quantization + jit cache + the full
    prediction surface.  ``eval_fn(compiled, X) → (B, C)`` is the engine's
    pure evaluator; ``compiled`` carries ``transform_inputs`` when the
    forest is quantized.

    Every call runs the host path ``_score``, whose steps are profiler
    spans (``repro.obs.trace.phase``); subclasses change only the
    ``_program``/``_bucket``/``_tile``/``_launch``/``_untile`` steps
    and what ``launch_args`` says of the shape they run at."""

    #: the autotuner's name of the engine (``EngineSpec.tune_name``),
    #: stamped by ``EngineSpec.builder``; "" for a hand-built predictor
    tune_name = ""
    #: a tiled engine's tile, for ``launch_args``
    tile: Optional[dict] = None

    def __init__(self, compiled, eval_fn: Callable):
        self.compiled = compiled
        self._eval = eval_fn
        self._fn = jax.jit(lambda X: eval_fn(compiled, X))

    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        t = getattr(self.compiled, "transform_inputs", None)
        X = np.asarray(X)
        return t(X) if t is not None else X

    def predict_transformed(self, Xq: np.ndarray) -> np.ndarray:
        """Evaluate inputs that already went through ``transform_inputs``
        — the cascade's per-stage entry point, so a K-stage cascade
        quantizes each row once instead of once per surviving stage."""
        return self._score(Xq)

    def folds_inputs(self, X: np.ndarray) -> bool:
        """Whether ``transform_inputs`` leaves rows like ``X`` off the
        forest's grid, its input quantization folded into the program's
        node thresholds (Pallas predictors of quantized forests;
        docs/QUANT.md "Threshold folding")."""
        return False

    def predict(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X)
        with phase("quantize", rows=len(X), folded=self.folds_inputs(X)):
            Xq = self.transform_inputs(X)
        return self.predict_transformed(Xq)

    def _score(self, Xq) -> np.ndarray:
        """Transformed rows → host scores: pad to the row bucket, copy in,
        launch, wait, copy out.  ``repro.wait`` then ``repro.d2h`` is the
        one sync point, the work ``np.asarray`` on the output alone does:
        the copy to the host is queued before the wait, so it starts when
        the program ends and not when the host sees it end."""
        Xq = np.asarray(Xq)
        rows = Xq.shape[0]
        bucket = self._bucket(rows)
        fn = self._program(Xq)
        with phase("tile_pad", rows=rows, bucket=bucket):
            Xp = self._tile(Xq, bucket)
        with phase("h2d", bytes=Xp.nbytes):
            x = jnp.asarray(Xp)
        args = self.launch_args
        with phase("launch", **args):
            y = self._launch(fn, x)
        with phase("wait"):
            y.copy_to_host_async()
            y.block_until_ready()
        with phase("d2h", bytes=y.nbytes):
            out = self._untile(np.asarray(y), rows)
        count_tree_rows(args["engine"], rows * args["trees"])
        return out

    @functools.cached_property
    def launch_args(self) -> dict:
        """The ``repro.launch`` span's arguments: the engine, the
        forest's trees and mask words (``Forest.n_words``, the leaf
        bitvector's 32-bit words), and a tiled engine's ``tile``."""
        f = self.host_forest()
        return {"engine": self.tune_name or type(self).__name__,
                "trees": 0 if f is None else int(f.n_trees),
                "words": 0 if f is None else int(f.n_words),
                **(self.tile or {})}

    def _program(self, Xq: np.ndarray):
        """The device program for transformed rows ``Xq``."""
        return self._fn

    def _bucket(self, rows: int) -> int:
        """Rows the device program takes for a ``rows``-row call."""
        return rows

    def _tile(self, Xq: np.ndarray, bucket: int) -> np.ndarray:
        return ensure_feature_column(Xq)

    def _launch(self, fn, x):
        return fn(x)

    def _untile(self, out: np.ndarray, rows: int) -> np.ndarray:
        return out

    def predict_class(self, X: np.ndarray) -> np.ndarray:
        return self.predict(X).argmax(axis=1)

    def host_forest(self):
        """The host IR, if this predictor can reach one (compiled objects
        carry it for input quantization; CompiledRS nests it under qs)."""
        for owner in (self, getattr(self, "compiled", None),
                      getattr(getattr(self, "compiled", None), "qs", None)):
            f = getattr(owner, "forest", None)
            if f is not None:
                return f
        return None

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        # the normalization mode is a property of the *model*: vote-mass
        # leaves (all >= 0) sum-normalize, logit leaves softmax — decided
        # from the leaf table so results never depend on batch composition
        forest = self.host_forest()
        votes = None if forest is None else votes_mode(forest)
        return normalize_scores(self.predict(X), votes=votes)


def count_tree_rows(engine: str, n: int) -> None:
    """Add ``n`` scored rows × trees to ``repro_tree_rows_total{engine}``
    on the default registry, when observability is on."""
    reg = _obs_metrics.get_registry()
    if reg.enabled:
        reg.counter("repro_tree_rows_total",
                    "Rows x trees scored by predictors",
                    labels=("engine",)).labels(engine=engine).inc(float(n))


# --------------------------------------------------------------------------- #
# The registry
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class EngineSpec:
    """One engine × backend entry.

    Either ``build`` (forest, **kw → predictor) is set directly (Pallas
    backends), or ``compile`` + ``evaluate`` are set and ``build`` is
    derived via ``predictor_cls`` — the split form is what tree-sharding
    needs (it re-runs ``evaluate`` inside ``shard_map``).
    """
    name: str                             # canonical name, e.g. "bitvector"
    backend: str                          # "jax" | "pallas"
    tune_name: str                        # autotuner short name, e.g. "qs"
    build: Optional[Callable] = None      # (forest, **kw) -> Predictor
    compile: Optional[Callable] = None    # (forest, **kw) -> compiled arrays
    evaluate: Optional[Callable] = None   # (compiled, X) -> (B, C) jnp
    predictor_cls: type = BasePredictor
    shardable: bool = False               # supports core.shard tree-sharding
    shard_kw: Optional[Callable] = None   # (padded forest, n_shards) -> kw
    replicated: tuple = ()                # compiled fields never tree-sharded
    layout: Optional[Callable] = None     # (forest, plan) -> detail string;
    #                                       pipeline layout-pass hook
    serial_arrays: tuple = ()             # compiled array fields io.packed
    #                                       may serialize (dotted for nested
    #                                       dataclasses); empty → artifact
    #                                       not serializable, rebuild from
    #                                       the forest instead
    deferred: Optional[str] = None        # "module:attr" lazy build target
    doc: str = ""

    def builder(self) -> Callable:
        """The (forest, **kw) → predictor callable; what it builds
        carries this spec's ``tune_name``."""
        build = self._resolve()

        def stamped(forest, **kw):
            pred = build(forest, **kw)
            pred.tune_name = self.tune_name
            return pred

        return stamped

    def _resolve(self) -> Callable:
        if self.build is not None:
            return self.build
        if self.deferred is not None:
            mod, attr = self.deferred.split(":")
            fn = getattr(importlib.import_module(mod), attr)
            object.__setattr__(self, "build", fn)   # cache the resolution
            return fn
        if self.compile is None or self.evaluate is None:
            raise ValueError(f"engine {self.name}/{self.backend} registered "
                             "without build, deferred, or compile+evaluate")

        def build(forest, **kw):
            compiled = self.compile(forest, **kw)
            return self.predictor_cls(compiled, self.evaluate)

        object.__setattr__(self, "build", build)
        return build


_REGISTRY: dict[tuple[str, str], EngineSpec] = {}


def register_engine(name: str, *, backend: str = "jax",
                    tune_name: Optional[str] = None, **spec_kw):
    """Register an engine under (name, backend).

    Two forms:

      * call form — ``register_engine("bitvector", compile=compile_qs,
        evaluate=eval_batch, tune_name="qs", shardable=True)`` registers
        immediately and returns the ``EngineSpec``;
      * decorator form — ``@register_engine("gemm", backend="pallas")``
        above a ``(forest, **kw) → predictor`` builder.
    """
    def _store(spec: EngineSpec) -> EngineSpec:
        _REGISTRY[(spec.name, spec.backend)] = spec
        return spec

    tn = tune_name or name
    if any(k in spec_kw for k in ("build", "compile", "deferred")):
        return _store(EngineSpec(name=name, backend=backend, tune_name=tn,
                                 **spec_kw))

    def deco(fn):
        _store(EngineSpec(name=name, backend=backend, tune_name=tn,
                          build=fn, **spec_kw))
        return fn

    return deco


def register_deferred(name: str, *, backend: str, target: str,
                      tune_name: str, **spec_kw) -> EngineSpec:
    """Register an engine whose builder lives in a module we must not
    import eagerly (the Pallas kernels pull in the whole pallas stack)."""
    return register_engine(name, backend=backend, tune_name=tune_name,
                           deferred=target, **spec_kw)


def get(name: str, backend: str = "jax") -> EngineSpec:
    try:
        return _REGISTRY[(name, backend)]
    except KeyError:
        names = engines(backend)
        raise ValueError(
            f"unknown engine {name!r} for backend {backend!r}; "
            f"registered: {names or tuple(sorted(set(n for n, _ in _REGISTRY)))}"
        ) from None


def specs(backend: Optional[str] = None) -> tuple[EngineSpec, ...]:
    """All registered specs, in registration order."""
    return tuple(s for s in _REGISTRY.values()
                 if backend is None or s.backend == backend)


def engines(backend: Optional[str] = None) -> tuple[str, ...]:
    """Canonical engine names (deduped across backends, in order)."""
    return tuple(dict.fromkeys(s.name for s in specs(backend)))


def backends(name: str) -> tuple[str, ...]:
    return tuple(s.backend for s in _REGISTRY.values() if s.name == name)


def tune_table() -> dict[str, tuple[str, str]]:
    """Autotuner name → (engine, backend) — derived, never re-declared."""
    return {s.tune_name: (s.name, s.backend) for s in _REGISTRY.values()}


def by_tune_name(tune_name: str) -> EngineSpec:
    for s in _REGISTRY.values():
        if s.tune_name == tune_name:
            return s
    raise ValueError(f"unknown autotuner engine {tune_name!r}; "
                     f"registered: {sorted(tune_table())}")


def build(forest, name: str, backend: str = "jax", **kw) -> Predictor:
    """Compile ``forest`` with the registered (name, backend) engine."""
    return get(name, backend).builder()(forest, **kw)
