"""``mamba_chunk_scan``'s share of its roofline over the traced part of the
window: for every logged ``decode.prefill`` span that ended there, the time
its ``mamba_tokens`` (REAL prompt tokens x Mamba layers: a bucket's padding
is no work) need — the recurrence's bytes over the HBM rate or its
operations over the bf16 peak, whichever is longer
(``bench/work/mamba_chunk_scan.py``) — summed, over the kernel's measured
time (its own events on device 0 there). It swings with the prompts a 3 s
trace catches, as every prefill kernel's share does."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("mamba_chunk_scan")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    tokens = sum(r["attrs"]["mamba_tokens"] for r in span_log.records()
                 if r["name"] == "decode.prefill"
                 and "mamba_tokens" in r["attrs"] and lo <= r["t1"] < hi)
    if not tokens:
        return None
    return 100.0 * kernel.roofline_seconds(
        tokens, m["mamba_d_state"], m["mamba_expand"] * m["d_model"],
        run.samples["kv_itemsize"], run.peaks) / seconds
