"""``serve.ttft_p95_ms`` in a cell of the ``decode_open_loop_v2`` driver: the same
reader under a name of its own, as the ``.dp4`` readers are (a metric file
names its drivers, and the first file may not be edited)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve.ttft_p95_ms.py"))
LAYER, UNIT, MOVES, read = _of.LAYER, _of.UNIT, _of.MOVES, _of.read
DRIVERS = ("decode_open_loop_v2",)
