"""``decode.step_ms_mean`` in the ``qwen3_next_80b_a3b`` cell (driver
``decode_open_loop_v2``): the same reader under a name of its own, as the
``.v2``, ``.mla`` and ``.dp4`` readers are (a metric file names its drivers,
and the first file may not be edited)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.step_ms_mean.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
# the cell of this configuration reports serve_ttft_mean_ms (PERF.md
# section 2 says whether its TPOT p95 is bounded or recorded), so what this
# layer moves is named by the end-to-end metric the cell is sure to report
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
