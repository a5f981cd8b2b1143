"""Tree-sharded multi-device execution (docs/DESIGN.md §5).

Forest scoring is a sum over trees, so the natural multi-device layout is
**tree parallelism**: shard every per-tree compiled array across a 1-D
device mesh, evaluate the engine on each device's tree slice, and combine
the partial scores with a ``psum``.  Because every registered engine
compiles to a dataclass of tree-major arrays and exposes a pure
``evaluate(compiled, X)`` (see ``core/registry.py``), one generic wrapper
serves them all — no per-engine sharding code.

Mechanics:

  * the forest is padded with single-leaf zero-value trees to a multiple
    of the device count (they traverse to leaf 0 and contribute exactly
    0.0, so padding never changes the result);
  * the engine is compiled **once, globally** — static layout decisions
    (bitmm's field width, tree_chunk, gemm's Bvec) are identical on every
    device, which per-shard compilation could not guarantee;
  * compiled arrays whose leading axis is the tree axis get
    ``PartitionSpec("trees")``; everything else (unique-node tables,
    scalars, the host Forest) is replicated — the split is derived from
    the dataclass fields plus the spec's ``replicated`` names;
  * partial scores are exact under quantization: integer leaf sums divide
    by a power-of-two scale, so the psum reassociation is bitwise
    lossless and sharded == single-device.

Works on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see ``tests/test_shard.py``) and unchanged on real TPU meshes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import registry
from .forest import Forest
from .quantize import quantize_inputs
from .registry import BasePredictor


def pad_forest_trees(forest: Forest, mult: int) -> Forest:
    """Pad the ensemble with single-leaf zero trees to ``T % mult == 0``.

    A padding tree has no internal nodes and one leaf worth 0.0: every
    engine routes all instances to leaf 0 and adds nothing."""
    T = forest.n_trees
    pad = (-T) % mult
    if pad == 0:
        return forest

    def rows(a, fill=0):
        shape = (pad,) + a.shape[1:]
        return np.concatenate([a, np.full(shape, fill, dtype=a.dtype)])

    return replace(
        forest,
        n_trees=T + pad,
        feature=rows(forest.feature, -1),        # -1 → padding node
        threshold=rows(forest.threshold),
        left=rows(forest.left),
        right=rows(forest.right),
        leaf_lo=rows(forest.leaf_lo),
        leaf_mid=rows(forest.leaf_mid),
        leaf_hi=rows(forest.leaf_hi),
        leaf_value=rows(forest.leaf_value),
        n_nodes=rows(forest.n_nodes),
        n_leaves_per_tree=rows(forest.n_leaves_per_tree, 1),
    )


# --------------------------------------------------------------------------- #
# Generic compiled-dataclass partitioning
# --------------------------------------------------------------------------- #
def _partition(compiled, n_trees: int, replicated: tuple):
    """Split a compiled dataclass into (sharded, replicated, rebuild).

    Array fields with leading dim == n_trees are tree-sharded, other
    arrays replicated, non-array fields (ints, floats, the host Forest)
    baked in as statics.  Nested compiled dataclasses (CompiledRS.qs)
    recurse.  Returns flat dicts keyed by dotted field path and a
    ``rebuild(sharded, replicated)`` closure usable inside a trace."""
    sharded: dict = {}
    repl: dict = {}

    def walk(obj, prefix: str):
        cls = type(obj)
        statics = {}
        builders = {}
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            path = f"{prefix}{f.name}"
            if isinstance(v, Forest) or not (
                    dataclasses.is_dataclass(v)
                    or isinstance(v, (jnp.ndarray, np.ndarray))):
                statics[f.name] = v
            elif dataclasses.is_dataclass(v):
                builders[f.name] = walk(v, path + ".")
            elif (v.ndim >= 1 and v.shape[0] == n_trees
                  and f.name not in replicated):
                sharded[path] = jnp.asarray(v)
            else:
                repl[path] = jnp.asarray(v)

        def build(sh, rp, _cls=cls, _statics=statics, _builders=builders,
                  _prefix=prefix):
            kw = dict(_statics)
            for name, sub in _builders.items():
                kw[name] = sub(sh, rp)
            for f in dataclasses.fields(_cls):
                path = f"{_prefix}{f.name}"
                if path in sharded:
                    kw[f.name] = sh[path]
                elif path in repl:
                    kw[f.name] = rp[path]
            return _cls(**kw)

        return build

    rebuild = walk(compiled, "")
    return sharded, repl, rebuild


class ShardedPredictor(BasePredictor):
    """Predictor running one engine tree-sharded over a device mesh."""

    def __init__(self, forest: Forest, spec, fn, sharded, repl,
                 n_devices: int):
        # BasePredictor.__init__ is bypassed: the jit'd fn closes over the
        # mesh, not a single compiled object.
        self.forest = forest
        self.engine = spec.name
        self.tune_name = spec.tune_name
        self.spec = spec
        self.n_devices = n_devices
        self._sharded = sharded
        self._repl = repl
        self._fn = fn

    def transform_inputs(self, X: np.ndarray) -> np.ndarray:
        return quantize_inputs(self.forest, np.asarray(X))

    def _launch(self, fn, x):
        return fn(self._sharded, self._repl, x)


def tree_sharded(forest: Forest, engine: str = "bitvector", *,
                 n_devices: Optional[int] = None, devices=None,
                 **engine_kw) -> ShardedPredictor:
    """Compile ``engine`` with its trees sharded across ``n_devices``.

    Wraps any registered XLA engine (``spec.shardable``); outputs are
    identical to the single-device predictor (bitwise on quantized
    forests — partial sums reassociate losslessly, see module docstring).
    """
    spec = registry.get(engine, "jax")
    if not spec.shardable:
        raise ValueError(
            f"engine {engine!r} is not shardable (registered engines that "
            f"are: {[s.name for s in registry.specs('jax') if s.shardable]})")
    devs = list(devices if devices is not None else jax.devices())
    D = int(n_devices) if n_devices is not None else len(devs)
    if D > len(devs):
        raise ValueError(f"n_devices={D} but only {len(devs)} devices "
                         "visible (on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    devs = devs[:D]

    padded = pad_forest_trees(forest, D)
    kw = dict(engine_kw)
    if spec.shard_kw is not None:
        for k, v in spec.shard_kw(padded, D).items():
            kw.setdefault(k, v)
    compiled = spec.compile(padded, **kw)
    sharded, repl, rebuild = _partition(compiled, padded.n_trees,
                                        spec.replicated)
    if not sharded:
        # e.g. a caller-forced bitmm tree_chunk that does not divide the
        # padded tree count re-pads inside compile — replicating those
        # arrays would silently double-count trees under psum
        raise ValueError(
            f"engine {engine!r}: no compiled array has the {padded.n_trees}"
            "-tree leading axis; refusing to shard")

    mesh = Mesh(np.asarray(devs), ("trees",))
    s_specs = jax.tree.map(lambda _: P("trees"), sharded)
    r_specs = jax.tree.map(lambda _: P(), repl)
    # place the forest on the mesh once, so a call moves only X
    sharded = jax.device_put(sharded, NamedSharding(mesh, P("trees")))
    repl = jax.device_put(repl, NamedSharding(mesh, P()))

    def _eval(sh, rp, X):
        local = rebuild(sh, rp)
        return jax.lax.psum(spec.evaluate(local, X), "trees")

    # check_vma=False: engine loops carry values that start replicated
    # (zeros) and become device-varying (tree-sharded partial sums);
    # the engines are written per device, not for varying-axis typing
    fn = jax.jit(shard_map(_eval, mesh=mesh,
                           in_specs=(s_specs, r_specs, P()),
                           out_specs=P(), check_vma=False))
    # the quantization metadata lives on the *original* forest; padding
    # preserves it (dataclasses.replace), so transform_inputs matches
    return ShardedPredictor(padded, spec, fn, sharded, repl, D)
