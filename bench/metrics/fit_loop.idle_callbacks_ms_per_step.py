"""Device-0 idle time per whole ``bench.fit_step`` of the traced window while
the host was in the ``batch_end_callback``s (the benchmark's own loss fetch
is one).

Reads the logged ``train.callbacks`` spans
(``mxnet_tpu.tracing.span_log()``) against ``run.trace.gaps``;
``bench/span_log.py`` has the arithmetic and the guard on the two clocks.
The five ``fit_loop.idle_*`` metrics add up to the device's idle time per
step; a reading of a millisecond or two is under the split's floor (the
device plane's clock is not held to the host plane's:
``bench/span_log.py``) and judges nothing."""
from bench import span_log

LAYER = "fit loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    return span_log.fit_idle_ms(run, "train.callbacks")
