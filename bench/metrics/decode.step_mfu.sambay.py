"""The serving path's share of the chip's bf16 peak over the measured
window for the ``phi4_mini_flash_reasoning`` configuration: the model
FLOPs (``bench/work/phi4_mini_flash_reasoning.py``: a prompt's self-decoder
over its positions and its cross-decoder over its last, every decoded token
through all 32 layers with its logits) of every prompt whose prefill began
inside the window and of every token a decode step made inside it, over
the window's length times the peak. A token k of a request (k >= 1) is
placed at ``t_first + k x (t_done - t_first) / (tokens - 1)``. An
end-to-end utilization, not a roofline share: the share of the whole step
that a later claim on a kernel of this cell is bounded by."""

LAYER = "DecodeEngine step"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


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
                flops += work.token_flops(model, r.prompt_len + k - 1)
    return 100.0 * flops / ((hi - lo) * run.peaks["bf16_flops_per_s"])
