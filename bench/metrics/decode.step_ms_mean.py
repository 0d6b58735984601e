"""Mean wall of one decode step (one token for every live slot, the host
fetch of the tokens included): the delta of the ``decode/step_seconds``
histogram's sum over the delta of its count, over the window."""

LAYER = "DecodeEngine step"
UNIT = "ms"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    c = run.samples["window_counts"]
    if not c["steps"]:
        return None
    return c["step_seconds"] / c["steps"] * 1e3
