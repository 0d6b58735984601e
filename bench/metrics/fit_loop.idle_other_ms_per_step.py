"""Device-0 idle time per whole ``bench.fit_step`` of the traced window while
the host was in none of the four logged phases (``span_log.FIT_PHASES``): the
data wait, ``prepare``, argument assembly around the program call, the loop
itself. Over a tenth of the five's sum, a phase is missing its span.

Reads ``run.trace.gaps`` less what the four phases' spans of
``mxnet_tpu.tracing.span_log()`` cover; ``bench/span_log.py`` has the
arithmetic and the guard on the two clocks. The five ``fit_loop.idle_*``
metrics add up to the device's idle time per step; a reading of a
millisecond or two is under the split's floor (the device plane's clock is
not held to the host plane's: ``bench/span_log.py``) and judges nothing."""
from bench import span_log

LAYER = "fit loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    return span_log.fit_idle_ms(run, span_log.OTHER)
