"""``moe.expert_ffn_roofline`` in the ``qwen3_next_80b_a3b`` cell: the grouped
expert kernel's share of its roofline at 2048 x 512, from the spans'
``moe_rows`` — the REAL rows that landed on one of the 64 experts this chip
holds, not the router's assignments — and ``moe_active_experts``
(``bench/work/moe_grouped_ffn.py``). The same reader under a name of its
own."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "moe.expert_ffn_roofline.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
