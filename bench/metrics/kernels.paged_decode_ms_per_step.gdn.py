"""``kernels.paged_decode_ms_per_step`` in the ``qwen3_next_80b_a3b`` cell:
the paged decode kernel's device time a decode step, three calls a step —
the 3 full-attention layers, 2 KV heads of 256 serving 8 query heads each,
pages of 512 tokens. The same reader under a name of its own."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kernels.paged_decode_ms_per_step.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
# the cell of this configuration reports serve_ttft_mean_ms (PERF.md
# section 2 says whether its TPOT p95 is bounded or recorded), so what this
# layer moves is named by the end-to-end metric the cell is sure to report
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
