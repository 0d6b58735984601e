"""The serving path's share of the chip's bf16 peak over the measured
window, for the ``deepseek_v3`` configuration as cut: the model FLOPs
(``bench/work/deepseek_v3.py``: the latent attention's maps, attention over
the positions before a token, the dense layer, router, shared expert, the
output map where logits are made) of every prompt whose prefill began
inside the window and of every token a decode step made inside it, plus the
routed experts' products of the rows REALLY computed here (the spans'
``moe_rows`` of the passes that began in the window), over the window's
length times the peak. A token k of a request (k >= 1) is placed at
``t_first + k x (t_done - t_first) / (tokens - 1)``. An end-to-end
utilization, not a roofline share: padding, dummy slots, the absorbed
attend's extra operations and waiting are the denominator's. The share of
the whole step that a later claim on a kernel is bounded by."""
from bench import span_log

LAYER = "DecodeEngine step"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    span = run.samples.get("window_host")
    if run.peaks is None or not span:
        return None
    work = run.work(run.config["work"])
    if not hasattr(work, "routed_flops"):
        return None
    model = run.config["model"]
    lo, hi = span
    flops = 0
    for r in run.samples["all_requests"]:
        if r.admit is not None and lo <= r.admit < hi:
            flops += work.prefill_flops(model, r.prompt_len)
        if r.first is None or r.done is None or r.tokens < 2:
            continue
        gap = (r.done - r.first) / (r.tokens - 1)
        for k in range(1, r.tokens):
            if lo <= r.first + k * gap < hi:
                flops += work.token_flops(model, r.prompt_len + k - 1)
    rows = sum(rec["attrs"]["moe_rows"] for rec in span_log.records()
               if rec["name"] in ("decode.step", "decode.prefill")
               and "moe_rows" in rec["attrs"] and lo <= rec["t0"] < hi)
    flops += work.routed_flops(model, rows)
    return 100.0 * flops / ((hi - lo) * run.peaks["bf16_flops_per_s"])
