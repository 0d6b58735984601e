"""Mean share of the engine's STATE ROWS that were held in a decode step of
the window. The engine has a row a slot beside the null row, a session holds
one exactly while it holds a slot, and a queued one holds none — so the
rows held in a step are its live rows, and this is the counters' reading of
``decode.slot_occupancy`` (tokens made by steps / steps / slots) under the
name of what those rows cost: 19.3 MB of state each, read and written by
every step. A DESCRIPTOR of how much of the 2.5 GB state pool a step
touches; it cannot refuse. The engine's ``decode/state_rows_free`` gauge
says the same as each request finds it, and the ``decode_open_loop_v2``
driver samples no gauge of that name (PERF.md section 7)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.slot_occupancy.py"))
LAYER, UNIT = _of.LAYER, _of.UNIT
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)


def read(run):
    if not any(run.config["model"].get("linear_layout") or ()):
        return None
    return _of.read(run)
