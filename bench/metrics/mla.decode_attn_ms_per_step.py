"""Device time of the absorbed latent-attention kernel (``mla_paged_decode``)
per decode step: the summed self time of the kernel's events on device 0 in
the traced part of the window, over the decode steps the engine counted
there. Only a decode step runs this kernel (a prefill attends its prompt
decompressed, under another name), so no split by span is needed. One call a
layer: five a step in the one cell that reports it."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    counts = run.samples["trace_counts"]
    if run.trace is None or not counts or not counts["steps"]:
        return None
    seconds, calls, _names = run.trace.seconds_matching(
        run.work("mla_paged_decode").TRACE_NAME)
    if not calls:
        return None
    return seconds / counts["steps"] * 1e3
