"""The forest kernels compile for a TPU v5e chip, at the chip smoke's
widths (1024 trees × 64 leaves, d=784, 10 classes, 2048 rows), and the
three Pallas engines at 255 leaves over 136 features (4096 rows).

No chip is needed: the TPU compiler is installed and compiles for a
described v5e topology.  Nothing runs, so these tests say nothing about
results or speed — only that Mosaic accepts each kernel (the program
holds a ``tpu_custom_call``) instead of refusing it, as it refused the
interpreter-only kernels before.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library at a time, and every test worker
imports this file.  The kernels take their mode from
``quickscorer_kernel.interpret_mode``, which is CPU-true here, so each
test steers it to the compiled path.
"""
import os

import numpy as np
import pytest

T, L, D, C, B = 1024, 64, 784, 10, 2048
BLOCK_B, BLOCK_T = 128, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def forest():
    from repro import core
    return core.random_forest_ir(T, L, D, n_classes=C, seed=0, full=False)


@pytest.fixture
def compiled_mode(monkeypatch):
    from repro.kernels import quickscorer_kernel
    monkeypatch.setattr(quickscorer_kernel, "interpret_mode", lambda: False)


def _compile(one_chip, fn, *arrays, rows=B, d=D):
    """Lower ``fn`` on shapes placed on the described chip and compile;
    ``fn`` takes a ``(rows, d)`` float32 block first."""
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=one_chip)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in arrays]
    return jax.jit(fn).lower(x, *shapes).compile()


@pytest.mark.parametrize("int_out", [False, True], ids=["f32", "int32"])
def test_qs_kernel_compiles(one_chip, forest, compiled_mode, int_out):
    import jax.numpy as jnp
    from repro.kernels import ops, quickscorer_kernel
    out = jnp.int32 if int_out else jnp.float32
    c = _compile(one_chip, lambda x, *a: quickscorer_kernel.qs_forward(
        x, *a, block_b=BLOCK_B, block_t=BLOCK_T, out_dtype=out),
        *ops._qs_arrays(forest, BLOCK_T))
    assert "tpu_custom_call" in c.as_text()


def test_bitmm_kernel_compiles(one_chip, forest, compiled_mode):
    from repro.kernels import ops, quickscorer_kernel
    *arrays, bits, npack = ops._bitmm_arrays(forest, BLOCK_T)
    c = _compile(one_chip, lambda x, *a: quickscorer_kernel.qs_bitmm_forward(
        x, *a, bits=bits, npack=npack, n_leaves=forest.n_leaves,
        block_b=BLOCK_B, block_t=BLOCK_T), *arrays)
    assert "tpu_custom_call" in c.as_text()


def test_gemm_kernel_compiles(one_chip, forest, compiled_mode):
    from repro.kernels import gemm_forest_kernel, ops
    c = _compile(one_chip, lambda x, *a: gemm_forest_kernel.gemm_forward(
        x, *a, block_b=BLOCK_B), *ops._gemm_arrays(forest, BLOCK_T))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("gate", ["margin", "proba", "bound"])
def test_fused_cascade_kernel_compiles(one_chip, forest, compiled_mode,
                                       gate):
    """Every built-in gate's ``decide`` lowers inside the kernel body."""
    import jax
    import jax.numpy as jnp
    from repro.cascade import MarginGate, ProbaGate, ScoreBoundGate
    from repro.kernels import cascade_kernel, ops
    stages = (T // 4, T)
    policy = {"margin": MarginGate, "proba": ProbaGate,
              "bound": ScoreBoundGate}[gate]()
    policy.prepare(forest, stages)
    *arrays, stage_tiles = ops._cascade_arrays(forest, stages, BLOCK_T)
    valid = jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=one_chip)

    def fn(x, v, *a):
        return cascade_kernel.cascade_qs_forward(
            x, v, *a, stage_tiles=stage_tiles, policy=policy,
            inv_scale=1.0, block_b=BLOCK_B, block_t=BLOCK_T)

    c = _compile(one_chip, fn, valid, *arrays)
    assert "tpu_custom_call" in c.as_text()


def test_tiling_the_chip_cannot_compile_is_refused(forest, compiled_mode):
    """On a TPU a batch block that is not a lane multiple is refused at
    build time, naming the bound — never compiled into a wrong kernel."""
    from repro.kernels.ops import pallas_qs_predictor
    with pytest.raises(ValueError, match="block_b % 128"):
        pallas_qs_predictor(forest, block_b=32)


def test_tile_over_vmem_budget_is_refused(compiled_mode):
    from repro import core
    from repro.kernels.ops import pallas_gemm_predictor
    wide = core.random_forest_ir(8, 64, 20000, seed=1)
    with pytest.raises(ValueError, match="scoped VMEM budget"):
        pallas_gemm_predictor(wide)


def test_interpreter_only_on_cpu():
    import inspect

    import jax
    from repro.kernels import ops, quickscorer_kernel
    assert quickscorer_kernel.interpret_mode() == (
        jax.default_backend() == "cpu")
    for build in (ops.pallas_qs_predictor, ops.pallas_bitmm_predictor,
                  ops.pallas_gemm_predictor, ops.pallas_fused_cascade_qs):
        assert "interpret" not in inspect.signature(build).parameters


def test_tile_layout_at_compiled_widths(forest):
    """Host layout sanity at the compiled widths: node rows are
    lane-dense (1, block_t × slots) with slots a multiple of 8, and the
    bias slot of every real tree fires."""
    from repro.kernels import ops
    feat, thr, masks, leaf = ops._qs_arrays(forest, BLOCK_T)
    slots = feat.shape[-1] // BLOCK_T
    assert feat.shape == (T // BLOCK_T, 1, BLOCK_T * slots)
    assert slots % 8 == 0 and slots > forest.nodes_per_tree
    bias = thr.reshape(-1, slots, BLOCK_T)[:, forest.nodes_per_tree]
    assert np.all(bias == -np.inf)
    assert leaf.shape == (T // BLOCK_T, BLOCK_T * L, C)


def test_folded_qs_program_compiles(one_chip, forest, compiled_mode):
    """The program float32 rows of a quantized forest take (the clamp,
    then the QuickScorer kernel against the folded cutoffs) compiles:
    the predictor's own program, lowered for the described chip."""
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.kernels.ops import pallas_qs_predictor
    calib = np.random.default_rng(0).normal(size=(4096, D))
    qf = core.quantize_forest(forest, calib)
    pred = pallas_qs_predictor(qf, block_b=BLOCK_B, block_t=BLOCK_T)
    assert pred.folds_inputs(np.zeros((1, D), np.float32))
    x = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    c = pred._program_for(True).lower(x).compile()
    assert "tpu_custom_call" in c.as_text()


# trees of 255 leaves (8 mask words, 2048 node slots and leaf rows per
# tile) over 136 features, 4096 rows: the LightGBM ranker's widths, two
# tree tiles (a kernel's body is one tile's, whatever the tree count)
WIDE_T, WIDE_L, WIDE_D, WIDE_B = 16, 255, 136, 4096


@pytest.fixture(scope="module")
def wide_forest():
    from repro import core
    return core.random_forest_ir(WIDE_T, WIDE_L, WIDE_D, seed=3, full=False)


@pytest.mark.parametrize("kernel", ["qs", "bitmm", "gemm"])
def test_kernels_compile_at_255_leaves(one_chip, wide_forest, compiled_mode,
                                       kernel):
    from repro.kernels import gemm_forest_kernel, ops, quickscorer_kernel
    f = wide_forest
    assert f.n_words == 8
    if kernel == "qs":
        arrays = ops._qs_arrays(f, BLOCK_T)
        fn = lambda x, *a: quickscorer_kernel.qs_forward(   # noqa: E731
            x, *a, block_b=BLOCK_B, block_t=BLOCK_T)
    elif kernel == "bitmm":
        *arrays, bits, npack = ops._bitmm_arrays(f, BLOCK_T)
        fn = lambda x, *a: quickscorer_kernel.qs_bitmm_forward(  # noqa: E731
            x, *a, bits=bits, npack=npack, n_leaves=f.n_leaves,
            block_b=BLOCK_B, block_t=BLOCK_T)
    else:
        arrays = ops._gemm_arrays(f, BLOCK_T)
        fn = lambda x, *a: gemm_forest_kernel.gemm_forward(  # noqa: E731
            x, *a, block_b=BLOCK_B)
    assert arrays[0].shape[-1] == BLOCK_T * 256          # node slots a tile
    c = _compile(one_chip, fn, *arrays, rows=WIDE_B, d=WIDE_D)
    assert "tpu_custom_call" in c.as_text()
