"""``moe.expert_ffn_ms_per_step`` in the ``qwen3_next_80b_a3b`` cell: the
grouped expert kernel's device time inside decode steps (the driver's split
by span), a step; whole experts of 3 x 2048 x 512 fit VMEM, so each touched
expert is fetched once. Twelve calls a step. The same reader under a name
of its own (a metric's cells are listed by its entry)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "moe.expert_ffn_ms_per_step.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
