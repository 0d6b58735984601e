"""A tiny rehearsal of each cell's driver on the CPU, through the
benchmark's one command: the result line keeps to the contract, names the
CPU it ran on, and is ``correct`` — the module and the engine agree with
their plain references at the tiny size. A run that is not a rehearsal is
refused here, where there is no TPU. Each run is a process of its own, as
the driver's are."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BM = json.load(f)
CELLS = [w["name"] for w in BM["workloads"]]


def bench(*args):
    env = dict(os.environ, BENCH_RUN="7")       # the driver's own; ignored
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py")] + list(args),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_the_traced_run(cell):
    line = last_line(bench("--workload", cell, "--seed", "3000000019",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # the CPU is named, so none of this can pass for a device number
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["kind"] == "cpu"
    per_layer = set(m["name"] for m in BM["per_layer"]
                    if cell in m.get("workloads", CELLS))
    assert line["metrics"] and set(line["metrics"]) <= per_layer
    # nothing that only a chip can give is reported from the CPU
    for name in line["metrics"]:
        source = next(m["source"] for m in BM["per_layer"]
                      if m["name"] == name)
        assert source != "device_trace", name
        assert "mfu" not in name and "roofline" not in name
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_rehearsal_of_the_plain_run_reports_the_end_to_end_metrics():
    cell = CELLS[0]
    line = last_line(bench("--workload", cell, "--seed", "5", "--seconds",
                           "1", "--trace", "0", "--rehearse-cpu"))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    want = set(m["name"] for m in BM["end_to_end"]
               if cell in m.get("workloads", CELLS))
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_without_a_tpu_nothing_is_measured():
    proc = bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
