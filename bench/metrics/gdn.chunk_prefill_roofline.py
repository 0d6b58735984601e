"""``gdn_chunk_prefill``'s share of its roofline over the traced part of the
window: for every logged ``decode.prefill`` span that ended there, the time
its ``linear_tokens`` (REAL prompt tokens x linear layers: a bucket's
padding is no work) need — the recurrence's operations over the bf16 peak or
the tokens' vectors over the HBM rate, whichever is longer
(``bench/work/gdn_chunk_prefill.py``: counted from the recurrence, not from
the chunk the kernel picked) — summed, over the kernel's measured time (its
own events on device 0 there). It swings with the prompts a 3 s trace
catches, as every prefill kernel's share does."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("gdn_chunk_prefill")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    tokens = sum(r["attrs"]["linear_tokens"] for r in span_log.records()
                 if r["name"] == "decode.prefill"
                 and "linear_tokens" in r["attrs"] and lo <= r["t1"] < hi)
    if not tokens:
        return None
    return 100.0 * kernel.roofline_seconds(
        tokens, m["linear_key_heads"], m["linear_value_heads"],
        m["linear_key_dim"], m["linear_value_dim"],
        run.samples["kv_itemsize"], run.peaks) / seconds
