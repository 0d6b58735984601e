"""``moe_grouped_ffn``'s share of its roofline over the traced part of the
window: for every logged ``decode.step`` and ``decode.prefill`` span that
ended there, the larger of the bytes it had to move over the HBM rate (the
three maps of every expert that received a row — the span's
``moe_active_experts`` — and its rows in and out) and its operations over
the bf16 peak (``moe_rows`` gated MLPs; ``bench/work/moe_grouped_ffn.py``),
summed, over the kernel's measured time (all its events on device 0
there). A decode step's calls are bound by HBM, a long prefill's by the
MXU; the padding rows and padding tiles the kernel computes are not work."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
# the cell of this driver reports no serve_tpot_p95_ms (its p95 over 43
# requests spreads past that bound, PERF.md section 7), so what its step
# moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("moe_grouped_ffn")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    need = 0.0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] in ("decode.step", "decode.prefill")
                and "moe_rows" in attrs and lo <= r["t1"] < hi):
            need += kernel.roofline_seconds(
                attrs["moe_rows"], attrs["moe_active_experts"],
                m["d_model"], m["d_ff"], run.samples["kv_itemsize"],
                run.peaks)
    if not need:
        return None
    return 100.0 * need / seconds
