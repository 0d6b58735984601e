"""Mean ``context_tokens`` of the measured window's logged ``decode.step``
spans: the positions one step attends over, summed over its live rows —
the engine's own stamp of the attention kernel's work per step. It
DESCRIBES the traffic a run drew (it follows the seed's prompts) and is
nothing a layer optimises: ``better`` has to say something and says
``lower`` because more context makes a step longer; read a change in
``decode.step_ms_mean`` or a kernel's time against it, never it alone."""
from bench import span_log, stats

LAYER = "DecodeEngine step"
UNIT = "tokens"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    return stats.mean(k["attrs"]["context_tokens"]
                      for _it, kids in passes for k in kids
                      if k["name"] == "decode.step"
                      and "context_tokens" in k["attrs"])
