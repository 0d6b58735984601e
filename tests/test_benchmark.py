"""Benchmark record store + measurement gates (mxnet_tpu/benchmark.py):
every record names the device it ran on, the newest record wins, an
unknown device has no peak, and nothing is read back as a result."""
import importlib
import json
import os

import pytest

import mxnet_tpu as mx
from mxnet_tpu import health


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_BENCH_DIR", str(tmp_path))
    import mxnet_tpu.benchmark as B
    importlib.reload(B)
    yield B
    monkeypatch.delenv("MXNET_TPU_BENCH_DIR")
    importlib.reload(B)


@pytest.fixture()
def known_device(monkeypatch):
    import jax
    monkeypatch.setitem(health.DEVICE_PEAKS, jax.devices()[0].device_kind,
                        {"flops": 197e12, "int8_ops": 393e12,
                         "hbm_bytes_per_s": 819e9})


def test_newest_record_replaces_previous_even_lower(bench):
    bench.persist("m", 500.0, "img/s")
    bench.persist("m", 300.0, "img/s")
    assert bench.load_results()["m"]["value"] == 300.0
    # other metrics are kept
    bench.persist("n", 1.0, "x")
    assert set(bench.load_results()) == {"m", "n"}


def test_record_names_the_device_it_ran_on(bench):
    import jax
    rec = bench.persist("m", 1.0, "img/s", {"batch": 32})
    d = jax.devices()[0]
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
        d.platform, d.device_kind, len(jax.devices()))
    assert rec["batch"] == 32 and "harness" not in rec
    on_disk = json.load(open(bench.RESULTS_PATH))["m"]
    assert on_disk["platform"] == "cpu"


def test_device_enumeration_failure_is_not_swallowed(bench, monkeypatch):
    import jax

    def boom():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        bench.persist("m", 1.0, "img/s")
    assert not os.path.exists(bench.RESULTS_PATH)


def test_load_results_tolerates_missing_and_corrupt_store(bench):
    assert bench.load_results() == {}
    os.makedirs(bench.BENCH_DIR, exist_ok=True)
    with open(bench.RESULTS_PATH, "w") as f:
        f.write('{"m": {"val')
    assert bench.load_results() == {}


class _Trainer:
    def init(self, dshape, lshape):
        import numpy as np
        return {"w": np.zeros(2)}, {}, {}

    def stage(self, d, l):
        return d, l

    def step(self, p, m, a, d, l):
        import numpy as np
        return p, m, a, np.float32(0.1)


def test_train_gate_rejects_above_peak(bench, known_device):
    import time as _time
    real_time = _time.time
    ticks = iter([0.0, 0.0, 1e-9])
    bench.time.time = lambda: next(ticks, real_time())
    try:
        with pytest.raises(RuntimeError, match="implausible"):
            # claims ~10^12 img/s: the MFU gate must refuse to record
            bench._measure_train(_Trainer(), batch=32, image=(3, 224, 224),
                                 num_classes=10, iters=1, dtype="float32",
                                 fwd_gflop_per_img=8.18, warmup=0)
    finally:
        bench.time.time = real_time


def test_mfu_on_unknown_device_kind_is_an_error(bench):
    """The host CPU is not in the peaks table: a job that prices its
    reading against a peak must fail, not borrow a v5e's roof."""
    with pytest.raises(mx.base.MXNetError, match="no published peak"):
        bench._measure_train(_Trainer(), batch=32, image=(3, 224, 224),
                             num_classes=10, iters=1, dtype="float32",
                             fwd_gflop_per_img=8.18, warmup=0)
    # without a peak-priced figure the same harness still measures
    img_s, extra = bench._measure_train(
        _Trainer(), batch=32, image=(3, 224, 224), num_classes=10,
        iters=1, dtype="float32", warmup=0)
    assert img_s > 0 and "mfu_est" not in extra


def test_run_driver_runs_tracked_source_without_temp_script(bench,
                                                           tmp_path):
    src = ("import json, os, sys\n"
           "import mxnet_tpu\n"            # repo root is on sys.path
           "print('MARK ' + json.dumps({'argv': sys.argv[1:],"
           " 'file': globals().get('__file__'),"
           " 'flag': os.environ['DRIVER_FLAG']}))\n")
    out = bench._run_driver(src, ["a", "7"], {"DRIVER_FLAG": "x"}, "MARK")
    assert out == {"argv": ["a", "7"], "file": None, "flag": "x"}
    with pytest.raises(RuntimeError, match="no MARK line.*boom"):
        bench._run_driver("raise SystemExit('boom')", [], {}, "MARK")


def test_every_job_is_a_callable_the_cli_accepts(bench):
    assert bench.JOBS and all(callable(j) for j in bench.JOBS.values())
    with pytest.raises(SystemExit):
        bench.main(["--job", "not-a-job"])
    for gone in ("probe_device", "HARNESS_GEN", "JOB_PRIORITY",
                 "_platform"):
        assert not hasattr(bench, gone)
