"""Device time of the collective ops (the GSPMD gradient all-reduce) per
training step on device 0, from the trace."""

LAYER = "dp mesh"
UNIT = "ms"
MOVES = "train_samples_per_s.dp4"
DRIVERS = ("fit_cli",)
STEP = "bench.fit_step"


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    steps = run.trace.count(STEP)
    if not steps or run.trace.collective_s <= 0:
        return None
    return run.trace.collective_s / steps * 1e3
