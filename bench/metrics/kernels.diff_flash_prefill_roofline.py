"""``diff_flash_prefill``'s share of its roofline over the traced part of
the window: for every logged ``decode.prefill`` span that ended there, the
operations differential attention needs over its prompt (its length is
``self_decoder_only_tokens`` + 1) in the full layer and in each window
layer (``bench/work/diff_flash_prefill.py``), over the bf16 peak, summed,
over the kernel's measured time (its own events on device 0 there). It
swings with the prompts a 3 s trace catches."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("diff_flash_prefill")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    own = [li for li in range(m["cross_from"]) if not m["mamba_layout"][li]]
    n_window = sum(1 for li in own if m["window_layout"][li])
    flops = sum(kernel.flops(r["attrs"]["self_decoder_only_tokens"] + 1,
                             len(own) - n_window, n_window,
                             m["sliding_window"], m["n_heads"],
                             m["head_dim"])
                for r in span_log.records()
                if r["name"] == "decode.prefill"
                and "self_decoder_only_tokens" in r["attrs"]
                and lo <= r["t1"] < hi)
    if not flops:
        return None
    return 100.0 * kernel.roofline_seconds(flops, run.peaks) / seconds
