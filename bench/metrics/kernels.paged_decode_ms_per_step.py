"""Device time of ``paged_decode_attention`` per decode step: the summed
self time of the kernel's events on device 0 in the traced part of the
window, over the decode steps the engine counted there
(``decode/step_seconds``' count). Found by the name the trace prints
(``bench/work/paged_decode_attention.py`` ``TRACE_NAME``)."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    counts = run.samples["trace_counts"]
    if run.trace is None or not counts or not counts["steps"]:
        return None
    seconds, calls, _names = run.trace.seconds_matching(
        run.work("paged_decode_attention").TRACE_NAME)
    if not calls:
        return None
    return seconds / counts["steps"] * 1e3
