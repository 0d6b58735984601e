"""Device time of the differential paged decode kernel
(``diff_paged_decode``) per decode step: the summed self time of its own
events on device 0 in the traced part of the window, over the decode steps
the engine counted there. Sixteen calls a step: 8 window layers over their
rings, the full layer and its 7 cross-attention readers over the one full
pool."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    counts = run.samples["trace_counts"]
    if run.trace is None or not counts or not counts["steps"]:
        return None
    seconds, calls, _names = run.trace.seconds_matching(
        run.work("diff_paged_decode").TRACE_NAME)
    if not calls:
        return None
    return seconds / counts["steps"] * 1e3
