"""``memory_stats()["peak_bytes_in_use"]`` after the window, the fullest of
the cell's chips, in GB (1e9 bytes)."""

LAYER = "device"
UNIT = "GB"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
