"""The serving path's share of the chip's bf16 peak over the measured
window: the model FLOPs (``bench/work/smallthinker.py``: projections,
router, the six experts, attention under each layer's rule, the output map
where logits are made) of every prompt whose prefill began inside the
window and of every token a decode step made inside it, over the window's
length times the peak. A token k of a request (k >= 1) is placed at
``t_first + k x (t_done - t_first) / (tokens - 1)`` — the engine stamps a
session's first and last token, and steps are regular between them. An
end-to-end utilization, not a roofline share: padding, dummy slots and
waiting are the denominator's."""

LAYER = "DecodeEngine step"
UNIT = "%"
# the cell of this driver reports no serve_tpot_p95_ms (its p95 over 43
# requests spreads past that bound, PERF.md section 7), so what its step
# moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    span = run.samples.get("window_host")
    if run.peaks is None or not span:
        return None
    work = run.work(run.config["work"])
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
                flops += work.token_flops(model, r.prompt_len + k - 1, True)
    return 100.0 * flops / ((hi - lo) * run.peaks["bf16_flops_per_s"])
