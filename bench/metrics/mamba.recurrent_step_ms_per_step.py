"""Device time of the Mamba recurrent kernel (``mamba_recurrent_step``) per
decode step: the summed self time of the kernel's own events on device 0 in
the traced part of the window, over the decode steps the engine counted
there. Only a decode step runs this kernel (a prefill runs the chunked
scan, under another name). One call a Mamba layer: nine a step."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    counts = run.samples["trace_counts"]
    if run.trace is None or not counts or not counts["steps"]:
        return None
    seconds, calls, _names = run.trace.seconds_matching(
        run.work("mamba_recurrent_step").TRACE_NAME)
    if not calls:
        return None
    return seconds / counts["steps"] * 1e3
