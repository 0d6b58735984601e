"""Percentile and open-loop arithmetic on made-up samples, and the device
check's refusals. CPU only; no jax import."""
import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, stats                            # noqa: E402


def test_percentile_interpolates_between_ranks():
    vals = [10, 20, 30, 40, 50]
    assert stats.percentile(vals, 0) == 10
    assert stats.percentile(vals, 50) == 30
    assert stats.percentile(vals, 95) == pytest.approx(48.0)
    assert stats.percentile(vals, 100) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([], 95) is None
    assert stats.percentile(list(reversed(vals)), 25) == 20


def test_spread_is_the_contracts():
    import statistics
    vals = [100, 101, 102, 103, 104, 110]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))


def test_ttft_counts_from_the_due_time_not_the_send_time():
    # the generator was 40 ms late: the user still waited from `due`
    r = stats.Request(due=1.0, sent=1.04, first=1.25, done=2.25, tokens=11,
                      want_tokens=11)
    assert stats.ttft_ms(r) == pytest.approx(250.0)
    assert stats.lateness_ms(r) == pytest.approx(40.0)
    assert stats.tpot_ms(r) == pytest.approx(100.0)
    assert not r.failed


def test_one_token_request_has_no_gap():
    r = stats.Request(due=0.0, sent=0.0, first=0.1, done=0.1, tokens=1,
                      want_tokens=1)
    assert stats.tpot_ms(r) is None and not r.failed


@pytest.mark.parametrize("kw", [
    dict(error="QueueFullError: refused"),              # refused at submit
    dict(sent=0.0, first=0.2, tokens=3),                # never finished
    dict(sent=0.0, first=0.2, done=0.9, tokens=3),      # short of its tokens
], ids=["refused", "timed_out", "truncated"])
def test_a_failed_request_misses_the_limit(kw):
    r = stats.Request(due=0.0, want_tokens=8, gave_up=60.0, **kw)
    assert r.failed
    ok = stats.Request(due=0.0, sent=0.0, first=0.2, done=1.0, tokens=8,
                       want_tokens=8)
    if r.first is None:
        # it is given the time until the benchmark gave up: the worst
        assert stats.ttft_ms(r) == pytest.approx(60000.0)
        assert stats.percentile(
            [stats.ttft_ms(x) for x in [ok] * 9 + [r]], 95) > 1000.0
        # and the mean, which is the end-to-end metric, takes it whole
        assert stats.mean([stats.ttft_ms(x) for x in [ok] * 9 + [r]]) \
            == pytest.approx((9 * 200.0 + 60000.0) / 10)


def test_mean_takes_every_request():
    assert stats.mean([100.0, 200.0, 600.0]) == pytest.approx(300.0)
    assert stats.mean([]) is None


Device = collections.namedtuple("Device", "platform device_kind")
PEAKS = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))


def test_peaks_table_has_the_v5e_with_its_source():
    row = PEAKS["devices"]["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in PEAKS["source"]


@pytest.mark.parametrize("devices,chips,why", [
    ([Device("cpu", "cpu")], 1, "not a TPU"),
    ([Device("tpu", "TPU v99")], 1, "not in bench/peaks.json"),
    ([Device("gpu", "H100")], 1, "not a TPU"),
    ([Device("tpu", "TPU v5 lite")], 4, "needs 4"),
], ids=["cpu", "unknown_kind", "gpu", "too_few_chips"])
def test_device_check_refuses(devices, chips, why):
    with pytest.raises(harness.Refused, match=why):
        harness.check_devices(devices, chips, PEAKS["devices"], False)


def test_device_check_accepts_the_chip_and_the_named_rehearsal():
    tpu = [Device("tpu", "TPU v5 lite")] * 4
    assert harness.check_devices(tpu, 4, PEAKS["devices"], False)[
        "hbm_bytes_per_s"] == 819e9
    assert harness.check_devices([Device("cpu", "cpu")], 1,
                                 PEAKS["devices"], True) is None
    with pytest.raises(harness.Refused):
        harness.check_devices(tpu, 1, PEAKS["devices"], True)
