"""95th percentile of the wait between two decode steps while sequences
were live: the start of a logged ``decode.step`` minus the end of the one
before, over the passes of the measured window that began with sequences
carried over (``decode.iteration``'s ``live`` > 0). Every live sequence
waits that long for its next token beyond the step itself: the pass's
expiry, admissions and prefills (no step runs during a prefill), the step's
assembly and the delivery of the tokens before."""
from bench import span_log, stats

LAYER = "DecodeEngine scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    passes = span_log.decode_window(run)
    if passes is None:
        return None
    gaps, before = [], None
    for it, kids in passes:
        for step in (k for k in kids if k["name"] == "decode.step"):
            if before is not None and it["attrs"].get("live", 0) > 0:
                gaps.append((step["t0"] - before) * 1e3)
            before = step["t1"]
    return stats.percentile(gaps, 95)
