"""Mean share of the full pool's pages that were reserved as each arrival of
the window found it, in the ``phi4_mini_flash_reasoning`` cell (the
engine's ``decode/pages_free{kind="global"}``, sampled by the driver right
after every ``submit`` due in the window). The full pool holds ONE layer,
the one the cross-attention layers read; a request reserves its whole
life's pages at admission, queued requests included."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.kv_pool_used_share.global.py"))
LAYER, UNIT = _of.LAYER, _of.UNIT
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v3",)


def read(run):
    return _of.read_kind(run, "global")
