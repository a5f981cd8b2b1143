"""Record ``cpu_program_spans.xplane.pb``, the CPU trace that
``test_bench_spans.py`` reads:

    PYTHONPATH=src JAX_PLATFORMS=cpu python bench/testdata/record_program_spans.py

A small quantized forest served by ``ServingRuntime`` on the calling
thread: one batch of four rows before ``bench.window`` opens (its
``repro.*`` spans lie outside the window), then three batches inside it,
each followed by a 3 ms ``bench.wait`` sleep that no ``repro.*`` span
covers.  The profiler runs as the harness runs it (``host_tracer_level``
1, no Python tracer).
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import numpy as np

from repro import core
from repro.inference import ServingRuntime

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "cpu_program_spans.xplane.pb")


def main() -> None:
    rng = np.random.default_rng(0)
    forest = core.random_forest_ir(n_trees=8, n_leaves=16, n_features=6,
                                   n_classes=3, seed=0)
    forest = core.quantize_forest(forest, rng.normal(size=(256, 6)))
    rt = ServingRuntime(obs=False)
    rt.add_model("m", core.compile_forest(forest, engine="bitvector"),
                 max_batch=4, max_wait_ms=1.0)
    rt.warmup()
    X = rng.normal(size=(4, 6))

    def batch():
        for row in X:
            rt.submit("m", row, arrival_s=0.0)
        rt.flush(now_s=1.0)

    batch()                                   # compiled and warm
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        batch()                               # before the window
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                batch()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copyfile(path, OUT)
    shutil.rmtree(tmp)
    rt.close()
    print(OUT, os.path.getsize(OUT), "bytes")


if __name__ == "__main__":
    main()
