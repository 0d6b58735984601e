"""% of the engine's ``decode.step`` spans that ended in the traced part
of the window with their ``ahead`` attribute true: steps dispatched while
the step before them still ran on the device, with its token array as
their input (``DecodeEngine``'s one step in flight). A step that was not
says why in ``drained_for``; the share is what the pipeline covered of
the steps.

The engine's own records are the ones that carry ``ahead`` (a request's
copy of a step carries ``slots``, ``bucket`` and ``live_pages`` only). A
program without the attribute, or an untraced run, reads ``None``."""
from bench import span_log

LAYER = "DecodeEngine step"
UNIT = "%"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop", "decode_open_loop_v2")


def read(run):
    window = run.samples.get("trace_host_window")
    if not window:
        return None
    p0, p1 = window
    flags = [r["attrs"]["ahead"] for r in span_log.records()
             if r["name"] == "decode.step" and "ahead" in r["attrs"]
             and p0 <= r["t1"] < p1]
    if not flags:
        return None
    return 100.0 * sum(1 for f in flags if f) / len(flags)
