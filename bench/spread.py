#!/usr/bin/env python
"""The spread behind a bound, from the result lines of two sets of runs.

    python bench/spread.py set1.jsonl set2.jsonl

Each file holds one result line (the benchmark's last line) per run of ONE
cell, the same seeds in both sets. Prints, per metric: each set's median
and spread — (Q3 - Q1) / median by ``statistics.quantiles(n=4)`` — the
wider spread, five times it (the bound the contract asks for, never under
1 %), and how far the second set's median lies from the first's.
"""
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import stats                                     # noqa: E402


def lines(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


def main(paths):
    sets = [lines(p) for p in paths]
    for k, runs in enumerate(sets):
        bad = [r for r in runs if not r["correct"] or r["failed"]]
        print("set %d: %d runs, %d not correct; device %s; peak %.3f GB"
              % (k + 1, len(runs), len(bad), runs[0]["device"]["kind"],
                 max(r["device"]["memory_peak_bytes"] for r in runs) / 1e9))
    for name in sets[0][0]["metrics"]:
        med, spr = [], []
        for runs in sets:
            vals = [r["metrics"][name]["value"] for r in runs]
            med.append(statistics.median(vals))
            spr.append(stats.spread(vals))
        wide = max(spr)
        row = "%-24s" % name + "".join(
            "  set%d median %.6g spread %.3f%%" % (k + 1, m, 100 * s)
            for k, (m, s) in enumerate(zip(med, spr)))
        row += "  -> widest %.3f%%, x5 = %.2f%%" % (100 * wide,
                                                   max(1.0, 500 * wide))
        if len(med) == 2:
            row += ", set2/set1 %+.2f%%" % (100 * (med[1] / med[0] - 1))
        print(row)


if __name__ == "__main__":
    main(sys.argv[1:])
