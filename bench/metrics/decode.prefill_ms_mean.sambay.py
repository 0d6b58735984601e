"""``decode.prefill_ms_mean`` in the ``phi4_mini_flash_reasoning`` cell: the
same reader under a name of its own. A prefill runs the self-decoder
(layers 0-17) over the prompt and the cross-decoder over its last
position alone."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.prefill_ms_mean.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)
