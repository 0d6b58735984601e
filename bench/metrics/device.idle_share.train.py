"""1 - (union of device-0 op intervals) / traced window, training cells."""

LAYER = "device"
UNIT = "%"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
