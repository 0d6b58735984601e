#!/usr/bin/env python
"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips: it loads, warms up, measures
for ``--seconds``, prints its lines, and last ONE JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). It refuses to measure off a TPU listed in
``bench/peaks.json``; ``--rehearse-cpu`` is the rehearsal asked for by name
(tiny files under ``bench/rehearsal/``, the CPU named in ``device``).
Everything about a cell is found by name from ``BENCHMARK.json`` — see
``bench/harness.py``.
"""
import time

T0 = time.perf_counter()            # set-up is counted from here

import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness           # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], ROOT, T0))
