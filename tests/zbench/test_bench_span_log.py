"""``bench/span_log.py`` on made-up traces and made-up span logs: the
overlap arithmetic of the ``fit_loop.idle_*`` metrics, the three
``decode.*`` span metrics, and both guards — a log whose clock does not run
with the trace's reads ``None``, and nothing raises on what a run may lack.
CPU only; the program's log is replaced by the made-up one."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, span_log                         # noqa: E402
from bench import trace_reduce as tr                        # noqa: E402

ORIGIN = 1000.0     # what the log's perf_counter reads at the trace's start
MS = 1e-3
CELL = harness.Cell(ROOT, "resnet50.fit_1chip")


def metric(name):
    return harness.load_module(CELL.metric_file(name))


def rec(name, start, end, span_id=None, parent_id=None, attrs=None):
    """A record of the log: ``start``/``end`` in trace seconds, stamped on
    a ``perf_counter`` that reads ``ORIGIN`` at the trace's start."""
    return {"name": name, "span_id": span_id or "%s@%r" % (name, start),
            "parent_id": parent_id, "attrs": attrs or {}, "status": "ok",
            "t0": ORIGIN + start, "t1": ORIGIN + end}


# -- training -----------------------------------------------------------------

STEP = 0.250
BOUNDS = [0.001 + k * STEP for k in range(7)]       # six whole steps
PHASES = ["warmup", "warmup", "window", "window", "edge"] + ["traced"] * 5 \
    + ["edge", "window"]
FIRST = PHASES.index("edge")


def fit_trace():
    events = [tr.Event(tr.HOST_PLANE, "python", tr.WINDOW_ANNOTATION,
                       BOUNDS[0] - 0.0005, BOUNDS[-1] - BOUNDS[0] + 0.001)]
    for b in BOUNDS[:-1]:
        events.append(tr.Event(tr.HOST_PLANE, "python", "bench.fit_step", b,
                               STEP))
        events.append(tr.Event("/device:TPU:0", tr.OPS_LINE, "fusion.1",
                               b + 70 * MS, 100 * MS))
    return tr.summarize(events, chips=1)


def fit_log():
    """One loop iteration a step: the callbacks span ends 20 us after the
    boundary the benchmark opens inside it. Device busy 70..170 ms after
    each boundary."""
    log = []
    for i in range(len(PHASES)):
        b = BOUNDS[0] + (i - FIRST) * STEP          # this callback's boundary
        log += [rec("train.callbacks", b - 8 * MS, b + 0.02 * MS),
                rec("executor.stage_input", b + 1 * MS, b + 31 * MS),
                rec("executor.train_step", b + 32 * MS, b + 72 * MS),
                rec("train.update_metric", b + 73 * MS, b + 190 * MS),
                rec("train.data_wait", b + 190 * MS, b + 240 * MS)]
    return log


def fit_run(log, monkeypatch, trace="made up", samples=None):
    monkeypatch.setattr(span_log, "records", lambda: log)
    steps = [{"phase": p, "wall": STEP} for p in PHASES]
    if samples is None:
        samples = {"warmup": steps[:2], "steps": steps[2:]}
    return types.SimpleNamespace(
        trace=fit_trace() if trace == "made up" else trace, samples=samples)


WANT = {"stage_input": 30.0, "dispatch": 38.0, "update_metric": 20.0,
        "callbacks": 8.02, "other": 53.98}
# the origin is found 20 us late (the callbacks span's end, not the
# boundary inside it), which moves an edge of two phases by as much
NEAR = 0.021


@pytest.mark.parametrize("suffix", ["", ".dp4"])
@pytest.mark.parametrize("short", sorted(WANT))
def test_idle_is_split_by_the_phase_the_host_was_in(short, suffix,
                                                    monkeypatch):
    run = fit_run(fit_log(), monkeypatch)
    mod = metric("fit_loop.idle_%s_ms_per_step%s" % (short, suffix))
    assert mod.read(run) == pytest.approx(WANT[short], abs=NEAR)


def test_the_five_add_up_to_the_devices_idle_time_per_step(monkeypatch):
    run = fit_run(fit_log(), monkeypatch)
    split = [span_log.fit_idle_ms(run, p)
             for p in span_log.FIT_PHASES + (span_log.OTHER,)]
    assert sum(split) == pytest.approx(150.0, abs=1e-6)
    # the window is the six steps and a millisecond: idle share x step
    assert sum(split) == pytest.approx(
        run.trace.idle_share * run.trace.window_s / 6 * 1e3, rel=0.01)


def test_origin_is_found_from_the_boundaries_despite_a_late_span_end(
        monkeypatch):
    """A pause between the benchmark's annotation and the end of the
    callbacks span (a collection, another thread) only ever makes a span
    end later: the least lead is the origin, and one late end moves
    nothing."""
    log = fit_log()
    late = [r for r in log if r["name"] == "train.callbacks"][FIRST + 1]
    late["t1"] += 3 * MS
    run = fit_run(log, monkeypatch)
    assert span_log.fit_idle_ms(run, "executor.stage_input") \
        == pytest.approx(30.0, abs=NEAR)


def shifted(log, from_s, by_ms):
    """``log`` with every stamp later than ``from_s`` (trace seconds) moved:
    a clock that stepped inside the window."""
    out = []
    for r in log:
        r = dict(r)
        if r["t0"] >= ORIGIN + from_s:
            r["t0"] += by_ms * MS
            r["t1"] += by_ms * MS
        out.append(r)
    return out


def drifting(log, rate):
    return [dict(r, t0=ORIGIN + (r["t0"] - ORIGIN) * rate,
                 t1=ORIGIN + (r["t1"] - ORIGIN) * rate) for r in log]


@pytest.mark.parametrize("why, log", [
    ("a clock that stepped by 5 ms in the middle of the window",
     lambda: shifted(fit_log(), BOUNDS[3] + 0.1, 5.0)),
    ("a clock that stepped back by 5 ms",
     lambda: shifted(fit_log(), BOUNDS[3] + 0.1, -5.0)),
    ("a clock running 1 % fast", lambda: drifting(fit_log(), 1.01)),
    ("a callbacks span lost",
     lambda: [r for r in fit_log() if r["name"] != "train.callbacks"
              or r["t0"] != fit_log()[0]["t0"]]),
    ("a callbacks span too many",
     lambda: fit_log() + [rec("train.callbacks", 9.0, 9.001)]),
    ("no log at all", lambda: []),
    ("a log without callbacks spans",
     lambda: [r for r in fit_log() if r["name"] != "train.callbacks"]),
], ids=lambda v: v if isinstance(v, str) else "")
def test_training_guard_reads_none_and_does_not_raise(why, log, monkeypatch,
                                                      capsys):
    run = fit_run(log(), monkeypatch)
    for phase in span_log.FIT_PHASES + (span_log.OTHER,):
        assert span_log.fit_idle_ms(run, phase) is None, why
    # the reason is printed once, not once per metric
    assert capsys.readouterr().out.count("nothing to read") == 1


@pytest.mark.parametrize("what", ["no trace", "no device plane",
                                  "no samples", "no step annotation"])
def test_training_reader_returns_none_on_what_a_run_may_lack(what,
                                                             monkeypatch):
    run = fit_run(fit_log(), monkeypatch)
    if what == "no trace":
        run.trace = None
    elif what == "no device plane":                 # the CPU rehearsal
        run.trace = tr.summarize([tr.Event(tr.HOST_PLANE, "python",
                                           "bench.fit_step", 0.0, 1.0)])
    elif what == "no samples":
        run.samples = {}
    else:
        run.trace.annotations.pop("bench.fit_step")
    assert metric("fit_loop.idle_other_ms_per_step").read(run) is None


def test_a_whole_log_shift_is_another_origin():
    """What the guard cannot see, said plainly: the xplane names its
    origin only inside the file, which ``Summary`` does not keep, so a log
    shifted as a whole reads as a trace that began at another instant
    (and the split with it: the floor ``bench/span_log.py`` states)."""
    steps = [(b, b + STEP) for b in BOUNDS[:-1]]
    cbs = span_log.named(fit_log(), "train.callbacks")
    origin, why = span_log.fit_origin(steps, PHASES, cbs)
    assert why is None and origin == pytest.approx(ORIGIN + 0.02 * MS,
                                                   abs=1e-9)
    moved = span_log.named(shifted(fit_log(), -100.0, 5.0),
                           "train.callbacks")
    origin, why = span_log.fit_origin(steps, PHASES, moved)
    assert why is None and origin == pytest.approx(ORIGIN + 5.02 * MS,
                                                   abs=1e-9)


def test_records_reads_the_programs_log_or_nothing(monkeypatch):
    from mxnet_tpu import tracing
    tracing.reset()
    with tracing.start_span("test.root"):
        pass
    got = span_log.records()
    assert [r["name"] for r in got] == ["test.root"]
    assert got[0]["t1"] >= got[0]["t0"] > 0
    # a program from before the log
    monkeypatch.delattr(tracing, "span_log")
    assert span_log.records() == []
    tracing.reset()


# -- serving ------------------------------------------------------------------

def decode_log(passes):
    """``passes``: [(start s, live, prefills, context_tokens)]. A pass is
    0.1 ms of scheduling, 77 ms a prefill, 0.3 ms of assembly, a 58 ms
    step, 0.5 ms of delivery and 0.1 ms of loop; only the prefills and the
    step have spans of their own."""
    log = []
    for n, (at, live, prefills, ctx) in enumerate(passes):
        sid, t = "it%d" % n, at
        kids = [(None, 0.1 * MS)]
        kids += [("decode.prefill", 77 * MS)] * prefills
        kids += [(None, 0.3 * MS), ("decode.step", 58 * MS), (None, 0.5 * MS)]
        for k, (name, dur) in enumerate(kids):
            attrs = {"context_tokens": ctx} if name == "decode.step" else {}
            if name is not None:
                log.append(rec(name, t, t + dur, "%s.%d" % (sid, k), sid,
                               attrs))
            t += dur
        log.append(rec("decode.iteration", at, t + 0.1 * MS, sid, None,
                       {"live": live}))
        # what a caller's context adds: the same interval, another parent
        log.append(rec("decode.step", at, t, "req%d" % n, "http", {}))
    return log


# back to back from 10.0 s; the pass at 10.4 s admits two requests; the
# engine then stands idle until 12.0 s (nobody waits over that hole)
PASSES = [(10.0, 0, 1, 100), (10.2, 1, 0, 110), (10.3, 1, 0, 120),
          (10.4, 1, 2, 130), (10.7, 3, 0, 140), (12.0, 0, 1, 150),
          (12.2, 1, 0, 160), (99.0, 1, 0, 999)]


def decode_run(log, monkeypatch, traced=True, counted=7,
               host_window=(ORIGIN + 9.5, ORIGIN + 12.5)):
    monkeypatch.setattr(span_log, "records", lambda: log)
    trace = None
    if traced:
        # no device plane, as in the CPU rehearsal: the annotation is there
        trace = tr.summarize([tr.Event(tr.HOST_PLANE, "python",
                                       tr.WINDOW_ANNOTATION, 9.5, 3.0)])
    due = [types.SimpleNamespace(due=ORIGIN + t) for t in (9.9, 10.3, 11.9)]
    return types.SimpleNamespace(trace=trace, samples={
        "requests": due, "window_s": 45.0,
        "trace_host_window": host_window if traced else None,
        "trace_counts": {"steps": counted} if traced else None})


def test_decode_metrics_read_the_iterations_of_the_window(monkeypatch):
    run = decode_run(decode_log(PASSES), monkeypatch)
    # the pass at 99 s began after the window (9.9 s + 45 s)
    host = metric("decode.host_ms_per_step").read(run)
    assert host == pytest.approx(1.0, abs=1e-6)      # 0.1+0.3+0.5+0.1
    ctx = metric("decode.step_context_tokens_mean").read(run)
    assert ctx == pytest.approx(sum(range(100, 170, 10)) / 7.0)
    gap = metric("decode.interstep_gap_ms_p95").read(run)
    # waits of the passes that carried sequences over: the passes at 10.2,
    # 10.3 and 12.2 wait for little more than the hole before them, the
    # one at 10.4 for two prefills besides; the one at 12.0 began with
    # nothing live, so the idle second before it is nobody's wait
    step_end = [at + (0.1 + 77 * n + 0.3 + 58) * MS
                for at, _l, n, _c in PASSES]
    step_start = [e - 58 * MS for e in step_end]
    waits = [(step_start[i] - step_end[i - 1]) * 1e3 for i in (1, 2, 3, 4, 6)]
    assert max(waits) == pytest.approx(400 - 300 - 58.4 + 154.4, abs=1e-3)
    from bench import stats
    assert gap == pytest.approx(stats.percentile(waits, 95), abs=1e-6)


@pytest.mark.parametrize("why, kwargs, log", [
    ("the run was not traced", {"traced": False},
     lambda: decode_log(PASSES)),
    ("steps missing from the log", {"counted": 12},
     lambda: decode_log(PASSES)),
    ("a clock that stepped by 5 ms inside the traced part",
     {"host_window": (ORIGIN + 9.5, ORIGIN + 12.505)},
     lambda: shifted(decode_log(PASSES), 11.0, 5.0)),
    ("a clock running 1 % fast",
     {"host_window": (ORIGIN + 9.5 * 1.01, ORIGIN + 12.5 * 1.01)},
     lambda: drifting(decode_log(PASSES), 1.01)),
    ("no decode.iteration in the log", {},
     lambda: [r for r in decode_log(PASSES) if r["parent_id"] == "http"]),
    ("no log at all", {}, lambda: []),
], ids=lambda v: v if isinstance(v, str) else "")
def test_serving_guard_reads_none_and_does_not_raise(why, kwargs, log,
                                                     monkeypatch):
    run = decode_run(log(), monkeypatch, **kwargs)
    for name in ("decode.host_ms_per_step", "decode.interstep_gap_ms_p95",
                 "decode.step_context_tokens_mean"):
        assert metric(name).read(run) is None, why


def test_serving_reader_returns_none_without_samples(monkeypatch):
    run = decode_run(decode_log(PASSES), monkeypatch)
    run.samples = {}
    assert metric("decode.host_ms_per_step").read(run) is None
