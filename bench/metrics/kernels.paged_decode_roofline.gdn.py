"""``paged_decode_attention``'s share of its roofline over the traced part
of the window in the ``qwen3_next_80b_a3b`` cell: every logged
``decode.step`` span that ended there carries ``context_tokens`` (the
positions its FULL layers attend, summed over the live rows; the linear
layers attend none); the bytes are the keys and values of those positions
in each of the 3 full layers — 2 KV heads of 256 — plus queries and outputs
(``bench/work/paged_decode_attention.py``), over the HBM rate, over the
kernel's measured time. Bound: HBM."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    window = run.samples["trace_host_window"]
    m = run.config["model"]
    if (run.trace is None or run.peaks is None or not window
            or not m.get("linear_layout")):
        return None
    kernel = run.work("paged_decode_attention")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    full = sum(1 for flag in m["linear_layout"][:m["n_layers"]] if not flag)
    nbytes = 0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] == "decode.step" and "linear_rows" in attrs
                and lo <= r["t1"] < hi):
            # the work function sums a list of contexts: one entry, the
            # step's total; queries and outputs are per call
            nbytes += full * kernel.bytes_per_layer_step(
                [attrs["context_tokens"]], m["n_kv_heads"], m["n_heads"],
                m["head_dim"], run.samples["kv_itemsize"])
    if not nbytes:
        return None
    return 100.0 * kernel.roofline_seconds(nbytes, run.peaks) / seconds
