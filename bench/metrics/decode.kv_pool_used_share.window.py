"""``decode.kv_pool_used_share.global``'s reader over the WINDOW layers'
pool, where a sequence holds at most a ring of ``window / page + 1`` pages."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.kv_pool_used_share.global.py"))
LAYER, UNIT, MOVES, DRIVERS = _of.LAYER, _of.UNIT, _of.MOVES, _of.DRIVERS


def read(run):
    return _of.read_kind(run, "window")
