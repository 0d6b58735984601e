"""Device-0 idle time per whole ``bench.fit_step`` of the traced window while
the host was binding the step's batch to the device(s):
``Executor._stage_input``'s ``device_put`` of the host batch (77 MB a step on
one chip, 308 MB on four).

Reads the logged ``executor.stage_input`` spans
(``mxnet_tpu.tracing.span_log()``) against ``run.trace.gaps``;
``bench/span_log.py`` has the arithmetic and the guard on the two clocks.
The five ``fit_loop.idle_*`` metrics add up to the device's idle time per
step; a reading of a millisecond or two is under the split's floor (the
device plane's clock is not held to the host plane's:
``bench/span_log.py``) and judges nothing."""
from bench import span_log

LAYER = "Executor fused step"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    return span_log.fit_idle_ms(run, "executor.stage_input")
