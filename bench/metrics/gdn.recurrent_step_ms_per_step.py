"""Device time of the recurrent Gated DeltaNet kernel
(``gdn_recurrent_step``) per decode step: the summed self time of the
kernel's own events on device 0 in the traced part of the window, over the
decode steps the engine counted there. Only a decode step runs this kernel
(a prefill runs the chunked one, under another name). One call a linear
layer: nine a step in the one cell that reports it."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    counts = run.samples["trace_counts"]
    if run.trace is None or not counts or not counts["steps"]:
        return None
    seconds, calls, _names = run.trace.seconds_matching(
        run.work("gdn_recurrent_step").TRACE_NAME)
    if not calls:
        return None
    return seconds / counts["steps"] * 1e3
