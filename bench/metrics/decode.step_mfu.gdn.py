"""The serving path's share of the chip's bf16 peak over the measured
window for the ``qwen3_next_80b_a3b`` configuration as cut
(``decode.step_mfu.mla``'s reader over ``bench/work/qwen3_next.py``: the
prompts prefilled and the tokens decoded in the window, the routed
products by the rows really computed, over the window times the peak). An
end-to-end utilization, not a roofline share: the share of the whole step
that a later claim on a kernel of this cell is bounded by."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.step_mfu.mla.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
