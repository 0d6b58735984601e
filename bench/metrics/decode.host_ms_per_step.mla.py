"""``decode.host_ms_per_step`` in the ``deepseek_v3`` cell (driver ``decode_open_loop_v2``): the
same reader under a name of its own, as the ``.v2`` and ``.dp4`` readers are
(a metric file names its drivers, and the first file may not be edited)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.host_ms_per_step.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
# the cell of this configuration reports serve_ttft_mean_ms and records its
# TPOT p95 as ``serve.tpot_p95_ms.mla`` (PERF.md section 2), so what its
# step moves is named by the end-to-end metric the cell does report: a new
# request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
