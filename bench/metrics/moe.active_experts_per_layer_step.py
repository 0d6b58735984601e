"""Mean number of experts, of a layer's, that received at least one row in
a decode step: the logged ``decode.step`` spans' ``moe_active_experts``
(the program's own count, fetched with the step's tokens, summed over the
layers) over the layers, over the measured window. It is what a step has
to read of a layer's expert weights. A DESCRIPTOR of the traffic and the
routing, with no better direction (``better`` says ``lower`` only because
fewer experts touched make a step shorter): read ``moe.expert_ffn_ms_per_
step`` against it, never it alone."""
from bench import span_log, stats

LAYER = "DecodeEngine step"
UNIT = "experts"
# the cell of this driver reports no serve_tpot_p95_ms (its p95 over 43
# requests spreads past that bound, PERF.md section 7), so what its step
# moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    layers = float(run.config["model"]["n_layers"])
    return stats.mean(k["attrs"]["moe_active_experts"] / layers
                      for _it, kids in passes for k in kids
                      if k["name"] == "decode.step"
                      and "moe_active_experts" in k["attrs"])
