"""The decompressed latent-attention prefill kernel's (``mla_flash_prefill``)
share of the bf16 peak over the traced part of the window: for every logged
``decode.prefill`` span that ended there, the operations its prompt's causal
attention needs — ``prompt_len x (prompt_len + 1) / 2`` (query, key) pairs a
head a layer, 2 x (nope + rope + v) each — over the peak, summed, over the
kernel's measured time (all its events on device 0 there). The kernel is
bound by the MXU (hundreds of FLOP a byte); what the padding of the bucket
and the masked half of the diagonal blocks cost is not work."""
from bench import span_log

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
# the custom-call is named after its jitted wrapper
TRACE_NAME = r"^_mla_flash_prefill(\.\d+)?$"


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    seconds, calls, _names = run.trace.seconds_matching(TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    m = run.config["model"]
    per_pair = 2 * m["n_heads"] * (m["qk_nope_head_dim"]
                                   + m["qk_rope_head_dim"] + m["v_head_dim"])
    flops = 0
    for r in span_log.records():
        attrs = r["attrs"]
        if (r["name"] == "decode.prefill" and "latent_context_tokens" in attrs
                and lo <= r["t1"] < hi):
            n = attrs["latent_context_tokens"] // m["n_layers"]
            flops += m["n_layers"] * per_pair * n * (n + 1) // 2
    if not flops:
        return None
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / seconds
