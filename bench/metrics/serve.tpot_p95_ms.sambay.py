"""95th percentile, over the requests due in the window, of ``(t_done -
t_first) / (tokens - 1)`` in the ``phi4_mini_flash_reasoning`` cell,
RECORDED beside what bounds the cell (PERF.md section 2 gives its spread
against ``serve_tpot_p95_ms``'s bound)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve.tpot_p95_ms.v2.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)
