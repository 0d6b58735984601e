"""``moe.local_assignment_share`` in the ``qwen3_next_80b_a3b`` cell: the
share of the router's ten-a-token assignments that landed on one of the 64
of 512 experts this chip holds (``moe_rows`` over ``moe_assignments``). A
DESCRIPTOR of the cut and the routing: an even router gives 1/8."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "moe.local_assignment_share.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
