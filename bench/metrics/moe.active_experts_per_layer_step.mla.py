"""Mean number of HELD experts, of an expert layer's, that received at
least one row in a decode step: the logged ``decode.step`` spans'
``moe_active_experts`` over the expert layers (the leading dense layers
have none), over the measured window — what a step has to read of a
layer's expert weights, of the 16 this chip holds. A DESCRIPTOR, as
``moe.active_experts_per_layer_step`` is: read ``moe.expert_ffn_ms_per_
step.mla`` against it, never it alone."""
from bench import span_log, stats

LAYER = "DecodeEngine step"
UNIT = "experts"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    m = run.config["model"]
    layers = float(m["n_layers"] - m.get("dense_layers", 0))
    return stats.mean(k["attrs"]["moe_active_experts"] / layers
                      for _it, kids in passes for k in kids
                      if k["name"] == "decode.step"
                      and "moe_active_experts" in k["attrs"])
