"""1 - (union of device-0 op intervals) / traced window, serving cells."""

LAYER = "device"
UNIT = "%"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
