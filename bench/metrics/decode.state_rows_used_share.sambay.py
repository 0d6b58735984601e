"""Mean share of the engine's state rows a decode step held, over the
logged ``decode.step`` spans that ended in the traced part of the window:
their ``state_rows`` (the live rows, each holding a row of 3.2 MB of Mamba
state and convolution tails, read and written by every step) over the
slots. A DESCRIPTOR of how much of the state pool a step touches; it
cannot refuse."""
from bench import span_log

LAYER = "DecodeEngine scheduler"
UNIT = "%"
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    window = run.samples["trace_host_window"]
    if not window:
        return None
    lo, hi = window
    rows = [r["attrs"]["state_rows"] for r in span_log.records()
            if r["name"] == "decode.step" and "state_rows" in r["attrs"]
            and lo <= r["t1"] < hi]
    if not rows:
        return None
    return 100.0 * sum(rows) / len(rows) / run.samples["slots"]
