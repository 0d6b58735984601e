"""The share of the collectives' device time during which no other op ran
on device 0: what the all-reduce costs that compute does not hide."""

LAYER = "dp mesh"
UNIT = "%"
MOVES = "train_samples_per_s.dp4"
DRIVERS = ("fit_cli",)


def read(run):
    if run.trace is None or run.chips < 2 or run.trace.collective_s <= 0:
        return None
    return 100.0 * run.trace.collective_exposed_s / run.trace.collective_s
