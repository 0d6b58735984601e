"""Share of the router's assignments that landed on an expert this chip
holds, over the measured window: the logged ``decode.step`` and
``decode.prefill`` spans' ``moe_rows`` over their ``moe_assignments``. A
DESCRIPTOR of the cut and the routing with no better direction (``better``
says ``higher`` only because it has to say something): 16 of 256 experts
and an even router give 1/16; a router that favoured the held experts
would make this chip's step longer and the deployment's no faster."""
from bench import span_log

LAYER = "DecodeEngine step"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    rows = assigned = 0
    for _it, kids in passes:
        for k in kids:
            if "moe_assignments" in k["attrs"]:
                rows += k["attrs"]["moe_rows"]
                assigned += k["attrs"]["moe_assignments"]
    if not assigned:
        return None
    return 100.0 * rows / assigned
