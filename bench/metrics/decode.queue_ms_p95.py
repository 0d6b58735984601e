"""95th percentile of the time a request waited for a slot: the session's
``t_admit - t_enq`` (the engine's own stamps), over the requests due in
the window."""
from bench import stats

LAYER = "DecodeEngine scheduler"
UNIT = "ms"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    waits = [(r.admit - r.enq) * 1e3 for r in run.samples["requests"]
             if r.admit is not None and r.enq is not None]
    return stats.percentile(waits, 95)
