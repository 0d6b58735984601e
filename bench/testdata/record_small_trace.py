#!/usr/bin/env python
"""Records ``small_trace.xplane.pb``, the trace ``tests/zbench`` checks
``bench/trace_reduce.py`` on. Run ON THE CHIP (one v5e), once:

    python bench/testdata/record_small_trace.py chiprun_out/small_trace.xplane.pb

Three "steps" inside the benchmark's host annotations, each a jitted
program of one matmul fusion and one fused SGD-momentum update kernel
(``ops/pallas/fused_update.py``, the kernel the training cells look for by
name), with a host sleep between steps so that the trace holds idle gaps.
"""
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from bench import trace_reduce                              # noqa: E402
from mxnet_tpu.ops.pallas import fused_update               # noqa: E402

HYPER = {"lr": 0.1, "wd": 1e-4, "rescale_grad": 1.0, "momentum": 0.9}


@jax.jit
def step(w, m, x):
    g = (x @ w) * 1e-3
    w, (m,) = fused_update.sgd_fused_update(w, g, (m,), HYPER)
    return w, m


def main(out):
    assert jax.devices()[0].platform == "tpu", "record this on the chip"
    w = jnp.ones((512, 512), jnp.float32)
    m = jnp.zeros((512, 512), jnp.float32)
    x = jnp.ones((512, 512), jnp.float32)
    w, m = jax.block_until_ready(step(w, m, x))
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.fit_step"):
                w, m = jax.block_until_ready(step(w, m, x))
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(tmp), out)
    shutil.rmtree(tmp)
    print("wrote %s (%d bytes)" % (out, os.path.getsize(out)))


if __name__ == "__main__":
    main(sys.argv[1])
