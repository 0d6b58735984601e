"""Mean number of HELD experts, of a layer's 64, that received at least one
row in a decode step (``moe.active_experts_per_layer_step.mla``'s reader:
the spans' ``moe_active_experts`` over the 12 layers, all of which route).
A DESCRIPTOR: read ``moe.expert_ffn_ms_per_step.gdn`` against it."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "moe.active_experts_per_layer_step.mla.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
