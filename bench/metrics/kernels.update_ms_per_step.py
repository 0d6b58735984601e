"""Device time of the fused optimizer-update kernels per training step:
the summed self time of the trace's update-kernel events on device 0 over
the whole steps traced. The events are found by the name the trace prints
for ``ops/pallas/fused_update.py``'s kernel (``bench/work/sgd_momentum.py``
``TRACE_NAME``); if a refactor renames it this returns nothing rather than
guess from op order."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)
STEP = "bench.fit_step"


def read(run):
    if run.trace is None:
        return None
    per_step = run.trace.seconds_per(
        run.work("sgd_momentum").TRACE_NAME, STEP)
    return None if per_step is None else per_step * 1e3
