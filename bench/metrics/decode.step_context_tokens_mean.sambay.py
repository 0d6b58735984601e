"""``decode.step_context_tokens_mean`` in the ``phi4_mini_flash_reasoning``
cell: the same reader under a name of its own. Here ``context_tokens``
counts the positions the full layer attends, which its 7 cross layers read
again; a window layer sees at most 512 of them and a Mamba layer a state
row whatever the context."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.step_context_tokens_mean.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)
