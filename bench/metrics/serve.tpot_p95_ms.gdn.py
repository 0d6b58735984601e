"""95th percentile, over the requests due in the window, of ``(t_done -
t_first) / (tokens - 1)``: what ``serve_tpot_p95_ms`` is in the first
serving cell, RECORDED here beside whatever bounds the cell (PERF.md
section 2 gives this cell's spreads over two sets of six seeds against
that metric's bound)."""
import os

from bench import harness

_of = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve.tpot_p95_ms.v2.py"))
LAYER, UNIT, MOVES, DRIVERS, read = (_of.LAYER, _of.UNIT, _of.MOVES,
                                     _of.DRIVERS, _of.read)
