"""Share of the engine's slots that carried a live sequence in a decode
step, over the window, from the counters: tokens made by steps (all tokens
less one per request, which its prefill made) / steps / slots. The step
program is chosen by the bucket of live slots and attends over every
live context, so the fuller the slots, the longer each step and with it
every request's time per output token."""

LAYER = "DecodeEngine scheduler"
UNIT = "%"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    c = run.samples["window_counts"]
    if not c["steps"]:
        return None
    return 100.0 * (c["tokens"] - c["prefills"]) / c["steps"] \
        / run.samples["slots"]
