"""``decode.interstep_gap_ms_p95`` in a cell of the ``decode_open_loop_v2`` driver: the same
reader under a name of its own, as the ``.dp4`` readers are (a metric file
names its drivers, and the first file may not be edited)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.interstep_gap_ms_p95.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
# the cell of this driver reports no serve_tpot_p95_ms (its p95 over 43
# requests spreads past that bound, PERF.md section 7), so what its step
# moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
