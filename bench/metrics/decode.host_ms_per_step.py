"""The host's own share of a decode pass: over the logged
``decode.iteration``s of the measured window that ran a step, the mean of
the pass's wall less its ``decode.prefill`` and ``decode.step`` children
(the two that wait for the device) — scheduling, the step's assembly, the
delivery of the tokens, the loop."""
from bench import span_log, stats

LAYER = "DecodeEngine step"
UNIT = "ms"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)
ON_DEVICE = ("decode.prefill", "decode.step")


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    return stats.mean(
        (it["t1"] - it["t0"]
         - sum(k["t1"] - k["t0"] for k in kids if k["name"] in ON_DEVICE))
        * 1e3
        for it, kids in passes
        if any(k["name"] == "decode.step" for k in kids))
