"""``decode.steps_ahead_share`` in the cells of the ``decode_open_loop_v2``
driver: the same reader under a name of its own, as the ``.v2`` readers are
(a metric's cells report what it moves). ``BENCHMARK.json`` lists it for
``decode_mixed_len`` alone: the rehearsal checks of ``decode_ctx5k`` and
``decode_long_answers`` admit only the readers their own configuration
brought."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode.steps_ahead_share.py"))
LAYER, UNIT, read = _of.LAYER, _of.UNIT, _of.read
# these cells report serve_ttft_mean_ms and not serve_tpot_p95_ms (PERF.md
# section 2): a new request waits for the running step before its prefill
MOVES = "serve_ttft_mean_ms"
DRIVERS = ("decode_open_loop_v2",)
