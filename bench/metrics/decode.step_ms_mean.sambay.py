"""``decode.step_ms_mean`` in the ``phi4_mini_flash_reasoning`` cell (driver
``decode_open_loop_v3``): the same reader under a name of its own, as the
``.v2``, ``.mla`` and ``.gdn`` readers are. A step advances every live row
through all 32 layers: 9 Mamba steps in place on the state pool, 16
differential paged attends (8 over window rings, 8 over the one full
layer's pages), 7 gated memory units."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.step_ms_mean.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)
