"""Compile requests that reached XLA inside the window: the delta of
``programs/compile_total`` + ``programs/disk_hits_total``. Must be 0, and
is part of ``correct``."""

LAYER = "program registry"
UNIT = "count"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    return run.counters["compiles_in_window"]
