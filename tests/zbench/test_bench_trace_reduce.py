"""``bench/trace_reduce.py`` on made-up events (the arithmetic) and on the
small trace recorded on the chip beside it (the file format and the names a
v5e trace really prints). CPU only: reading a trace needs no device."""
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402
from bench import trace_reduce as tr                        # noqa: E402

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def op(name, start, dur, plane=DEV0):
    return tr.Event(plane, tr.OPS_LINE, name, start, dur)


def host(name, start, dur):
    return tr.Event(tr.HOST_PLANE, "python", name, start, dur)


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]
    assert tr.clip([(0, 2), (3, 8)], 1, 5) == [(1, 2), (3, 5)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    events = [op("while", 0.0, 10.0), op("fusion.1", 1.0, 2.0),
              op("fusion.2", 4.0, 3.0), op("copy", 12.0, 1.0)]
    own = dict((e.name, t) for e, t in tr.self_times(events))
    assert own == {"while": 5.0, "fusion.1": 2.0, "fusion.2": 3.0,
                   "copy": 1.0}


def made_up():
    """Two steps in a 10 s window on two chips. Device 0 per step: a
    fusion, an all-reduce of which half runs beside another fusion, an
    update kernel; then idle until the next step."""
    events = [host(tr.WINDOW_ANNOTATION, 0.0, 10.0)]
    for k, t in enumerate((0.0, 5.0)):
        events += [
            host("bench.fit_step", t, 5.0),
            op("fusion.conv", t + 0.0, 2.0),
            op("all-reduce.7", t + 2.0, 1.0),
            op("fusion.bn", t + 2.5, 1.0),
            op("_update_kernel", t + 3.5, 0.5),
            op("fusion.conv", t + 0.0, 3.0, plane=DEV1),
        ]
    return events


def test_busy_idle_and_ops_by_name():
    s = tr.summarize(made_up(), chips=1)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_by_device == {0: pytest.approx(8.0)}
    assert s.busy_s == pytest.approx(8.0)
    assert s.idle_share == pytest.approx(0.2)
    assert s.count("bench.fit_step") == 2
    assert s.top_ops(1) == [["fusion.conv", pytest.approx(4.0)]]
    assert s.seconds_per(r"_update_kernel", "bench.fit_step") \
        == pytest.approx(0.5)
    assert s.seconds_per(r"no_such_kernel", "bench.fit_step") is None
    assert s.seconds_per(r"_update_kernel", "bench.no_such_span") is None


def test_busy_is_averaged_over_the_chips_used():
    s = tr.summarize(made_up(), chips=2)
    assert s.busy_by_device == {0: pytest.approx(8.0), 1: pytest.approx(6.0)}
    assert s.busy_s == pytest.approx(7.0)
    # per-op numbers and the idle share stay device 0's
    assert s.idle_share == pytest.approx(0.2)


def test_collective_time_and_its_exposed_part():
    s = tr.summarize(made_up(), chips=1)
    assert s.collective_s == pytest.approx(2.0)
    # each all-reduce runs 0.5 s alone and 0.5 s beside fusion.bn
    assert s.collective_exposed_s == pytest.approx(1.0)


def test_gaps_carry_the_innermost_host_annotation():
    events = made_up() + [host("bench.metric_fetch", 4.2, 0.6)]
    s = tr.summarize(events, chips=1)
    gaps = s.top_gaps(5)
    assert [sec for _n, sec in gaps] == [pytest.approx(1.0)] * 2
    assert sorted(n for n, _sec in gaps) == ["bench.fit_step",
                                             "bench.metric_fetch"]


def test_without_a_window_annotation_the_device_ops_span_it():
    events = [e for e in made_up() if e.plane != tr.HOST_PLANE]
    s = tr.summarize(events, chips=1)
    assert s.window == (0.0, 9.0)
    assert s.top_gaps(1) == [["no host annotation", pytest.approx(1.0)]]


def test_no_device_events_reduce_to_nothing():
    s = tr.summarize([host(tr.WINDOW_ANNOTATION, 0, 1)], chips=1)
    assert s.busy_s == 0.0 and s.window_s == 0.0 and s.top_ops(10) == []


RECORDED = os.path.join(ROOT, "bench", "testdata", "small_trace.xplane.pb")


def test_recorded_chip_trace():
    """Recorded on a v5e by ``bench/testdata/record_small_trace.py``: three
    annotated steps, each one matmul fusion and one fused-update kernel,
    2 ms of host sleep between them. In this recording the device's events
    sit 2-4 ms after the host spans that caused them, so the third step's
    ops fall behind the 10 ms window: two whole steps of device work are
    inside it. (A cell's window is seconds long and its steps a quarter of
    a second; there the offset does not matter.)"""
    events = tr.load(RECORDED)
    assert set(e.plane for e in events) == {DEV0, tr.HOST_PLANE}
    assert set(e.line for e in events if e.plane == DEV0) == {tr.OPS_LINE}
    s = tr.summarize(events, chips=1)
    assert s.count("bench.fit_step") == 3
    assert 0.006 < s.window_s < 0.5
    assert 0.0 < s.busy_s < s.window_s
    assert 0.5 < s.idle_share < 1.0         # the sleeps dominate
    # ops carry their short names; the whole HLO text stays beside them
    assert "convolution_multiply_fusion" in s.op_seconds
    assert s.op_text["convolution_multiply_fusion"].startswith(
        "%convolution_multiply_fusion = f32[512,512]")
    assert all(" = " not in name for name in s.op_seconds)
    # the fused-update kernel is found by its name, its neighbours by
    # dataflow: what it reads, and who reads its results
    rule = harness.Cell(ROOT, "resnet50.fit_1chip").work("sgd_momentum")
    seconds, calls, names = s.seconds_matching(rule.TRACE_NAME)
    assert names == ["_fused_update.1"] and calls == 2 and seconds > 0
    assert s.seconds_per(rule.TRACE_NAME, "bench.fit_step") \
        == pytest.approx(seconds / 3)
    feeds = tr.operands(s.op_text["_fused_update.1"])
    assert set(feeds) == {"copy-done.2", "reshape.1", "reshape.3",
                          "reshape.5"}
    readers = [n for n, text in s.op_text.items()
               if any(re.search(rule.RESULT_NAME, o)
                      for o in tr.operands(text))]
    assert sorted(readers) == ["reshape_reshape.0", "reshape_reshape.1"]
    gaps = s.top_gaps(5)
    assert gaps and all(n == "bench.fit_step" for n, _sec in gaps)
    assert gaps[0][1] >= 0.002
    assert s.collective_s == 0.0


def test_the_relayout_metric_follows_the_dataflow():
    """``kernels.update_relayout_ms_per_step`` on the recorded trace: the
    reshapes into and out of the kernel count, the matmul fusion that makes
    the gradient does not."""
    cell = harness.Cell(ROOT, "resnet50.fit_1chip")
    s = tr.summarize(tr.load(RECORDED), chips=1)

    class Run(object):
        trace = s
        work = staticmethod(cell.work)

    metric = harness.load_module(cell.metric_file(
        "kernels.update_relayout_ms_per_step"))
    want = sum(s.op_seconds[n] for n in (
        "reshape.1", "reshape.3", "reshape.5", "copy-done.2",
        "reshape_reshape.0", "reshape_reshape.1"))
    assert metric.read(Run) == pytest.approx(want / 3 * 1e3)
    assert want < sum(s.op_seconds.values())
