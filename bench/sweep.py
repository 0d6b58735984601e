#!/usr/bin/env python
"""Find a serving cell's knee ONCE, on the chip, when the cell is defined:
the same mix at several fixed rates, in one process (one compile, one
chip), each for ``--seconds`` after its ramp.

    python bench/sweep.py --workload cerebras_gpt_1p3b.decode_chat \
        --rates 2,3,4,5,6 --seconds 20 --seed 1

The knee is the highest rate with no refusals and no growing backlog: the
queue wait stays flat and the requests of the window drain soon after it.
Its 0.8-fold goes into the traffic file as ``rate_per_s``; the benchmark
itself never searches.
"""
import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, stats                            # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma list, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell = harness.Cell(ROOT, args.workload, rehearse=args.rehearse_cpu)
    import jax
    import mxnet_tpu  # noqa: F401
    peaks = harness.check_devices(
        jax.devices(), cell.chips, harness.load_json(os.path.join(
            cell.bench_dir, "peaks.json"))["devices"], args.rehearse_cpu)
    driver = harness.load_module(cell.driver_file)
    base = copy.deepcopy(cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(base, rate_per_s=rate)
        ctx = harness.Context(cell, args, time.perf_counter(),
                              jax.devices())
        result = driver.run(ctx)
        run = harness.Run(cell, peaks, result, None, 0)
        reqs = result["samples"]["requests"]
        row = {"rate_per_s": rate, "due": len(reqs),
               "failed": result["failed"],
               "ttft_p50_ms": stats.percentile(
                   [stats.ttft_ms(r) for r in reqs], 50),
               "tpot_p50_ms": stats.percentile(
                   [v for v in map(stats.tpot_ms, reqs) if v], 50),
               "checks_failed": [n for n, ok, _d in result["checks"]
                                 if not ok]}
        row.update(result["end_to_end"])
        row.update((k, v["value"]) for k, v in
                   harness.read_per_layer(cell, run).items())
        print("SWEEP " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
