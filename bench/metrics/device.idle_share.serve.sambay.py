"""``device.idle_share.serve`` in the ``phi4_mini_flash_reasoning`` cell: the
same reader under a name of its own."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "device.idle_share.serve.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)
