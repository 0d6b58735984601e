"""Device time of the grouped expert FFN kernel (``moe_grouped_ffn``) per
decode step: the kernel's events on device 0 in the traced part of the
window that began inside a logged ``decode.step`` span (the driver's split,
``samples["kernel_split"]``: a prefill runs the same kernel under the same
name, and its time is NOT counted here), over the decode steps the engine
counted there. Twelve calls a step in the one cell that reports it."""

LAYER = "Pallas kernels"
UNIT = "ms"
# the cell of this driver reports no serve_tpot_p95_ms (its p95 over 43
# requests spreads past that bound, PERF.md section 7), so what its step
# moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    counts = run.samples["trace_counts"]
    split = (run.samples.get("kernel_split") or {}).get("moe_grouped_ffn")
    if not counts or not counts["steps"] or not split or not split["calls"]:
        return None
    return split["step_s"] / counts["steps"] * 1e3
