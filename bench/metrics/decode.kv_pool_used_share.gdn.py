"""``decode.kv_pool_used_share.global``'s reading for a model with linear
layers: the pool of its FULL layers' pages (3 of 12 here), which the engine
reports under ``decode/pages_free{kind="global"}`` — the label the
``decode_open_loop_v2`` driver samples right after every ``submit`` due in
the window. A request reserves pages for its prompt and its whole answer at
admission, queued requests included; a pool near 100 % refuses
(``PagePoolExhausted``)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.kv_pool_used_share.global.py"))
LAYER, UNIT, MOVES, DRIVERS = _of.LAYER, _of.UNIT, _of.MOVES, _of.DRIVERS


def read(run):
    if not any(run.config["model"].get("linear_layout") or ()):
        return None
    return _of.read_kind(run, "global")
