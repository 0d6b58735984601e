"""``mla_paged_decode``'s share of its roofline over the traced part of the
window: for every logged ``decode.step`` span that ended there, the larger
of the bytes it had to move over the HBM rate (the span's
``latent_context_tokens`` cached vectors — the live rows' positions, times
the layers — plus a query and an output for each of its ``latent_rows``, a
row a layer) and its operations
over the bf16 peak (``bench/work/mla_paged_decode.py``: the ABSORBED count,
128 heads x 2 x (576 + 512) a vector, whatever the program does), summed,
over the kernel's measured time (all its events on device 0 there). At 242
FLOP/B the two bounds lie within a hundredth of each other."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("mla_paged_decode")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    width = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    need = 0.0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] == "decode.step" and "latent_context_tokens" in attrs
                and lo <= r["t1"] < hi):
            need += kernel.roofline_seconds(
                attrs["latent_context_tokens"],
                attrs.get("latent_rows", 0), m["n_heads"], width,
                m["kv_lora_rank"], run.samples["kv_itemsize"], run.peaks)
    if not need:
        return None
    return 100.0 * need / seconds
