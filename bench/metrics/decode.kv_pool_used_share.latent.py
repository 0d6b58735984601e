"""``decode.kv_pool_used_share.global``'s reading for a latent-attention
model's ONE pool: the engine sets ``decode/pages_free{kind="latent"}`` and,
because that pool keeps every position as a global layer's does, the same
value under ``kind="global"`` — the label the ``decode_open_loop_v2`` driver
samples right after every ``submit`` due in the window. A request reserves
pages for its prompt and its whole answer at admission, queued requests
included; a pool near 100 % refuses (``PagePoolExhausted``)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.kv_pool_used_share.global.py"))
LAYER, UNIT, MOVES, DRIVERS = _of.LAYER, _of.UNIT, _of.MOVES, _of.DRIVERS


def read(run):
    if not run.config["model"].get("kv_lora_rank"):
        return None
    return _of.read_kind(run, "global")
