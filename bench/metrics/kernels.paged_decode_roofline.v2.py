"""``paged_decode_attention``'s share of its roofline over the traced part
of the window, from the engine's own stamps (PERF.md section 7 (4)'s
repair, for cells of the ``decode_open_loop_v2`` driver): every logged
``decode.step`` span that ended there carries ``context_tokens`` (the
positions its global layers attend, summed over the live rows) and
``window_context_tokens`` (the same under the window); the bytes are the
keys and values of those positions in each kind's layers plus queries and
outputs (``bench/work/paged_decode_attention.py``), over the HBM rate, over
the kernel's measured time. Bound: HBM."""
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
    kernel = run.work("paged_decode_attention")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    n_window = sum(1 for x in m["window_layout"][:m["n_layers"]] if x)
    kinds = (("context_tokens", m["n_layers"] - n_window),
             ("window_context_tokens", n_window))
    nbytes = 0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] != "decode.step" or "window_context_tokens"
                not in attrs or not lo <= r["t1"] < hi):
            continue
        for key, layers in kinds:
            # the work function sums a list of contexts: one entry, the
            # step's total; queries and outputs are per call
            nbytes += layers * kernel.bytes_per_layer_step(
                [attrs[key]], m["n_kv_heads"], m["n_heads"], m["head_dim"],
                run.samples["kv_itemsize"])
    if not nbytes:
        return None
    return 100.0 * kernel.roofline_seconds(nbytes, run.peaks) / seconds
