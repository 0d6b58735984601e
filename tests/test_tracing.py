"""End-to-end tracing (mxnet_tpu/tracing.py): span contexts propagated
serve → batch → executor → kvstore, per-step train timelines, slow
exemplars, exporters, and the docs drift check.

Acceptance (ISSUE 5): one POST /predict through a warmed engine yields
one trace with >= 5 linked spans (http → queue → batch → forward →
slice) retrievable from /traces; a kvstore push under an injected
transient fault yields one client span with two attempt children, the
second marked retried.
"""
import importlib.util
import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault
from mxnet_tpu import io
from mxnet_tpu import profiler
from mxnet_tpu import telemetry as tm
from mxnet_tpu import tracing as tr
from mxnet_tpu.module import Module
from mxnet_tpu.serve import InferenceEngine, ServeConfig, serve_http
from mxnet_tpu.serving import Predictor

FEATURE = 4
CLASSES = 3


@pytest.fixture(autouse=True)
def _clean_tracer():
    prev_on = tr.enable(True)
    prev_rate = tr.set_sample(1.0)
    prev_slow = tr.set_slow_ms(1000)
    tr.reset()
    fault.disarm()
    yield
    fault.disarm()
    tr.set_slow_ms(prev_slow)
    tr.set_sample(prev_rate)
    tr.enable(prev_on)
    tr.reset()


def _model(tmp_path, seed=0):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(seed)
    path = str(tmp_path / "model.params")
    mx.nd.save(path, {
        "arg:fc_weight": mx.nd.array(
            rng.randn(CLASSES, FEATURE).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(
            rng.randn(CLASSES).astype(np.float32))})
    with open(path, "rb") as f:
        blob = f.read()
    return sym.tojson(), blob


def _engine(tmp_path, **cfg_kw):
    sym_json, blob = _model(tmp_path)
    pred = Predictor(sym_json, blob, input_shapes={"data": (1, FEATURE)})
    kw = dict(max_batch=4, queue_depth=32, batch_wait_ms=5,
              default_timeout_ms=10000, workers=1)
    kw.update(cfg_kw)
    return InferenceEngine(pred, ServeConfig(**kw))


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode()), dict(r.headers)


def _post(url, payload, headers=(), timeout=30):
    req = urllib.request.Request(
        url + "/predict", data=json.dumps(payload).encode(),
        headers=dict({"Content-Type": "application/json"}, **dict(headers)),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}"), dict(e.headers)


def _get_trace(base_url, trace_id, tries=50):
    """Fetch one trace by id, retrying briefly: the root span finalizes
    a hair after the HTTP response is written."""
    for _ in range(tries):
        try:
            _s, body, _h = _get(base_url + "/traces?id=" + trace_id)
            return body
        except urllib.error.HTTPError:
            time.sleep(0.02)
    raise AssertionError("trace %s never appeared" % trace_id)


def _by_name(trace, name):
    return [s for s in trace["spans"] if s["name"] == name]


# ---------------------------------------------------------------------------
# acceptance: serve path
# ---------------------------------------------------------------------------

def test_predict_trace_five_linked_spans(tmp_path):
    """One POST /predict through a warmed engine = one trace with >= 5
    linked spans, retrievable from /traces by the echoed request id."""
    eng = _engine(tmp_path).start()
    eng.warmup()
    srv = serve_http(eng)
    try:
        rid = "req-abc.123"
        status, body, headers = _post(
            srv.url, {"inputs": {"data": [[0.1] * FEATURE]}},
            headers=(("X-Request-Id", rid),))
        assert status == 200
        assert headers.get("X-Request-Id") == rid
        assert body["rows"] == 1

        trace = _get_trace(srv.url, rid)
        assert trace["trace_id"] == rid
        assert trace["root"] == "http.request"
        assert len(trace["spans"]) >= 5

        root = _by_name(trace, "http.request")[0]
        queue = _by_name(trace, "serve.queue_wait")[0]
        batch = _by_name(trace, "serve.batch")[0]
        compute = _by_name(trace, "serve.compute")[0]
        sliced = _by_name(trace, "serve.slice")[0]
        # linkage: http -> queue/batch -> compute/slice
        assert root["parent_id"] is None
        assert queue["parent_id"] == root["span_id"]
        assert batch["parent_id"] == root["span_id"]
        assert compute["parent_id"] == batch["span_id"]
        assert sliced["parent_id"] == batch["span_id"]
        # the executor's own span nests under serve.compute
        fwd = _by_name(trace, "executor.forward")
        assert fwd and fwd[0]["parent_id"] == compute["span_id"]
        # listing endpoint carries the trace too
        _s, listing, _h = _get(srv.url + "/traces")
        assert any(t["trace_id"] == rid for t in listing["recent"])
    finally:
        srv.close()
        eng.close(drain=False)


def test_request_id_echoed_on_error_responses(tmp_path):
    eng = _engine(tmp_path).start()
    eng.warmup()
    srv = serve_http(eng)
    try:
        # 400: malformed feed still echoes the id
        status, _b, headers = _post(
            srv.url, {"inputs": {"nope": [[1.0]]}},
            headers=(("X-Request-Id", "bad-input-1"),))
        assert status == 400
        assert headers.get("X-Request-Id") == "bad-input-1"
        # an invalid (header-splitting) id is replaced, not echoed
        status, _b, headers = _post(
            srv.url, {"inputs": {"data": [[0.1] * FEATURE]}},
            headers=(("X-Request-Id", "x" * 200),))
        assert status == 200
        got = headers.get("X-Request-Id")
        assert got and got != "x" * 200
    finally:
        srv.close()
        eng.close(drain=False)


def test_batch_span_fans_in_n_request_parents(tmp_path):
    """N concurrent requests coalesced into one batch: each trace gets
    the SAME serve.batch span id, parented under its own root."""
    eng = _engine(tmp_path, batch_wait_ms=200)
    eng.warmup()                          # compiled, workers NOT started
    done = []

    def client(i):
        with tr.start_span("test.root") as span:
            req = eng.submit({"data": [[0.1 * i] * FEATURE]},
                             ctx=span.ctx)
            req.result()
            done.append(span.trace_id)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)                       # all three queued
    eng.start()
    for t in threads:
        t.join()
    eng.close(drain=True)

    assert len(done) == 3
    traces = {tid: tr.get_trace(tid) for tid in done}
    assert all(t is not None for t in traces.values())
    batch_ids = set()
    for tid, t in traces.items():
        batches = _by_name(t, "serve.batch")
        assert len(batches) == 1
        assert batches[0]["attrs"]["fanin"] == 3
        root = _by_name(t, "test.root")[0]
        assert batches[0]["parent_id"] == root["span_id"]
        batch_ids.add(batches[0]["span_id"])
    assert len(batch_ids) == 1, "batch span id must be shared"


# ---------------------------------------------------------------------------
# acceptance: kvstore path
# ---------------------------------------------------------------------------

def test_kv_push_retry_one_client_span_two_attempts():
    """A push eating one injected transient fault = ONE kv.push client
    span with TWO kv.attempt children sharing it as parent, the second
    marked retried."""
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.array(np.zeros((2,), np.float32)))
    tr.reset()
    fault.arm("kv.push", step=1, kind="transient", count=1)
    with tr.start_span("test.root") as span:
        tid = span.trace_id
        kv.push("w", mx.nd.array(np.ones((2,), np.float32)))
    fault.disarm()

    t = tr.get_trace(tid)
    assert t is not None
    pushes = _by_name(t, "kv.push")
    assert len(pushes) == 1
    attempts = [s for s in _by_name(t, "kv.attempt")
                if s["parent_id"] == pushes[0]["span_id"]]
    assert len(attempts) == 2
    attempts.sort(key=lambda s: s["attrs"]["attempt"])
    assert attempts[0]["attrs"]["attempt"] == 1
    assert "retried" not in attempts[0]["attrs"]
    assert attempts[0]["status"] == "error"      # the injected fault
    assert attempts[1]["attrs"]["attempt"] == 2
    assert attempts[1]["attrs"]["retried"] is True
    assert attempts[1]["status"] == "ok"
    # a fault-injection hit always retains the trace as an exemplar
    assert any(x["trace_id"] == tid for x in tr.slow_traces())


def test_kv_server_roundtrip_context_propagation(monkeypatch):
    """Context rides the RPC payload: server handling (including the
    faulted first attempt) appears under the client's trace."""
    from mxnet_tpu.kvstore_server import KVStoreServer
    server = KVStoreServer(port=0, num_workers=1, sync_mode=True)
    server.start_background()
    monkeypatch.setenv("MXNET_TPU_PS_URI", "127.0.0.1")
    monkeypatch.setenv("MXNET_TPU_PS_PORT", str(server.port))
    monkeypatch.setenv("MXNET_KV_TIMEOUT_MS", "10000")
    try:
        kv = mx.kv.create("dist_sync")
        with tr.start_span("test.root") as span:
            tid = span.trace_id
            kv.init("w", mx.nd.array(np.zeros((3,), np.float32)))
            fault.arm("kv.server", step=1, kind="transient", count=1)
            kv.push("w", mx.nd.array(np.full((3,), 2.0, np.float32)))
            fault.disarm()
        t = tr.get_trace(tid)
        assert t is not None
        servers = [s for s in _by_name(t, "kv.server")
                   if s["attrs"].get("op") == "PUSH"]
        assert len(servers) == 2
        servers.sort(key=lambda s: s["t0"])
        assert servers[0]["status"] == "error"    # injected transient
        assert servers[1]["status"] == "ok"       # the retry
        # each server span parents to a distinct client attempt span
        attempt_ids = {s["span_id"] for s in _by_name(t, "kv.attempt")}
        assert servers[0]["parent_id"] in attempt_ids
        assert servers[1]["parent_id"] in attempt_ids
        assert servers[0]["parent_id"] != servers[1]["parent_id"]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# sampling, rings, retention
# ---------------------------------------------------------------------------

def test_sampling_honored():
    tr.set_sample(0.0)
    with tr.start_span("test.root"):
        with tr.child_span("test.child"):
            pass
    assert tr.finished_traces() == []
    tr.set_sample(1.0)
    with tr.start_span("test.root"):
        pass
    assert len(tr.finished_traces()) == 1


def test_unsampled_context_is_noop_scope():
    tr.set_sample(0.0)
    with tr.start_span("test.root") as span:
        assert span is tr.NOOP
        assert tr.active() is None


def test_tracer_does_not_consume_global_rng():
    """Ids and sampling decisions come from a private Random instance:
    a user's random.seed(...) stream must not diverge based on how many
    spans happened to be recorded."""
    import random
    random.seed(123)
    expect = [random.random() for _ in range(5)]
    random.seed(123)
    with tr.start_span("test.root"):
        with tr.child_span("test.child"):
            pass
    assert [random.random() for _ in range(5)] == expect


def test_ring_bounded():
    cap = tr._ring.maxlen
    for _ in range(cap + 25):
        with tr.start_span("test.root"):
            pass
    assert len(tr.finished_traces()) == cap


def test_slow_and_error_exemplars_retained():
    # fast + clean: NOT retained as an exemplar
    tr.set_slow_ms(10000)
    with tr.start_span("test.root"):
        pass
    assert tr.slow_traces() == []
    # slow: retained
    tr.set_slow_ms(0)
    with tr.start_span("test.root") as span:
        slow_tid = span.trace_id
    assert any(t["trace_id"] == slow_tid for t in tr.slow_traces())
    # error: retained regardless of the threshold
    tr.set_slow_ms(10000)
    with pytest.raises(RuntimeError):
        with tr.start_span("test.root") as span:
            err_tid = span.trace_id
            raise RuntimeError("boom")
    retained = [t for t in tr.slow_traces() if t["trace_id"] == err_tid]
    assert retained and "boom" in retained[0]["error"]


def test_transient_child_error_does_not_taint_trace():
    """A child failure that never reaches the root — a transport
    attempt retried to success, without fault injection — keeps its own
    error status but does not mark the trace errored, so routine
    transient noise cannot evict real exemplars from the error ring."""
    tr.set_slow_ms(10000)
    with tr.start_span("test.root") as span:
        tid = span.trace_id
        with pytest.raises(ValueError):
            with tr.child_span("test.child"):
                raise ValueError("transient")
    t = tr.get_trace(tid)
    assert t["error"] is None
    child = [s for s in t["spans"] if s["name"] == "test.child"][0]
    assert child["status"] == "error"
    assert not any(x["trace_id"] == tid for x in tr.slow_traces())


def test_graft_clock_rebases_foreign_epoch_only():
    """graft(): a bundle from another process (foreign proc token) is
    rebased by the clock-pair offset; a same-process bundle — e.g. a
    seq-cache replay re-shipping spans recorded seconds ago — keeps its
    true times."""
    now = time.perf_counter()

    def bundle(sid):
        return [{"name": "kv.server", "trace_id": "t" * 32,
                 "span_id": sid, "parent_id": "p" * 16,
                 "t0": now - 5.0, "t1": now - 4.9, "attrs": {},
                 "status": "ok", "tid": 1}]

    with tr.start_span("graft.root") as root:
        ctx = root.ctx
        tid = ctx.trace_id
        tr.graft(bundle("a" * 16), ctx=ctx,
                 clock=(tr._PROC_TOKEN, now, now + 0.5))
        tr.graft(bundle("b" * 16), ctx=ctx,
                 clock=("other-proc", now - 100.0, now))
    t = tr.get_trace(tid)
    same = [s for s in t["spans"] if s["span_id"] == "a" * 16][0]
    foreign = [s for s in t["spans"] if s["span_id"] == "b" * 16][0]
    assert same["t0"] == pytest.approx(now - 5.0, abs=1e-9)
    assert foreign["t0"] == pytest.approx(now - 5.0 + 100.0, abs=1e-6)


def test_late_spans_attach_after_root_finalized():
    """A span recorded after the root finalized — a worker finishing a
    batch whose requester already timed out (504) — still lands in the
    retained exemplar trace, with its phase in the breakdown."""
    tr.set_slow_ms(0)
    with tr.start_span("late.root") as root:
        ctx = root.ctx
        tid = ctx.trace_id
    t = tr.get_trace(tid)
    assert all(s["name"] != "late.child" for s in t["spans"])
    t0 = time.perf_counter()
    tr.record_span("late.child", ctx, t0, t0 + 0.005)
    t2 = tr.get_trace(tid)
    late = [s for s in t2["spans"] if s["name"] == "late.child"]
    assert len(late) == 1
    assert t2["phases"].get("late.child", 0.0) >= 4.0
    # dedup still applies through the late path
    tr.record_span("late.child", ctx, t0, t0 + 0.005,
                   span_id=late[0]["span_id"])
    assert len([s for s in tr.get_trace(tid)["spans"]
                if s["name"] == "late.child"]) == 1


def test_queue_expired_request_gets_queue_wait_span(tmp_path):
    """A request that dies in the queue (504) must still show WHERE the
    time went: its retained error exemplar carries a serve.queue_wait
    span covering the whole wait."""
    from mxnet_tpu.serve.engine import _Request
    eng = _engine(tmp_path)
    with tr.start_span("test.root") as root:
        tid = root.trace_id
        req = _Request({"data": np.zeros((1, FEATURE), np.float32)}, 1,
                       tm.monotonic() - 0.5, tctx=tr.current())
        req.t_enq = tm.monotonic() - 0.6
        eng._run_batch([req])
        with pytest.raises(Exception):
            req.result()
    t = tr.get_trace(tid)
    waits = [s for s in t["spans"] if s["name"] == "serve.queue_wait"]
    assert len(waits) == 1
    assert (waits[0]["t1"] - waits[0]["t0"]) >= 0.5
    assert t["error"] is not None           # retained as a 504 exemplar


def test_disabled_is_noop():
    tr.enable(False)
    with tr.start_span("test.root") as span:
        assert span is tr.NOOP
    assert tr.current() is None
    assert tr.finished_traces() == []
    tr.enable(True)


def test_span_cap_bounds_trace_memory():
    with tr.start_span("test.root") as span:
        tid = span.trace_id
        for _ in range(tr._MAX_SPANS + 50):
            with tr.child_span("test.child"):
                pass
    t = tr.get_trace(tid)
    assert len(t["spans"]) <= tr._MAX_SPANS + 1
    assert t["dropped_spans"] >= 50
    # the root envelope survives the cap even though it finishes last —
    # a capped trace must not be a bag of orphans
    assert _by_name(t, "test.root")


# ---------------------------------------------------------------------------
# train timeline
# ---------------------------------------------------------------------------

def _mlp_sym():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc", num_hidden=8)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_train_step_timeline_and_checkpoint_spans(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.rand(40, 16).astype(np.float32)
    labels = rng.randint(0, 8, size=(40,)).astype(np.float32)
    it = io.NDArrayIter(data, labels, batch_size=20)
    mod = Module(_mlp_sym(), context=mx.cpu())
    prefix = str(tmp_path / "ckpt")
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1),),
            checkpoint_prefix=prefix)

    steps = [t for t in tr.finished_traces() if t["root"] == "train.step"]
    assert steps, "no train.step traces recorded"
    phases = steps[-1]["phases"]
    for want in ("train.forward_backward", "train.update",
                 "train.data_wait"):
        assert want in phases, (want, phases)
    ckpts = [t for t in tr.finished_traces()
             if t["root"] == "train.checkpoint"]
    assert ckpts, "no train.checkpoint trace recorded"
    assert any("ckpt.write" == s["name"] for s in ckpts[-1]["spans"])


def test_io_batch_wait_span_under_step():
    rng = np.random.RandomState(0)
    base = io.NDArrayIter(rng.rand(8, 4).astype(np.float32),
                          np.zeros(8, np.float32), batch_size=4)
    pf = io.PrefetchingIter(base)
    with tr.start_span("test.root") as span:
        tid = span.trace_id
        for _batch in pf:
            pass
    t = tr.get_trace(tid)
    assert _by_name(t, "io.batch_wait")


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_export_valid_and_monotonic(tmp_path):
    with tr.start_span("test.root") as span:
        tid = span.trace_id
        with tr.child_span("test.child"):
            time.sleep(0.002)
    path = str(tmp_path / "trace.json")
    profiler.dump(finished=True, filename=path)
    with open(path) as f:
        doc = json.load(f)                # valid JSON by json.load
    spans = [e for e in doc["traceEvents"]
             if e.get("cat") == "trace"
             and e["args"].get("trace_id") == tid]
    assert len(spans) == 2
    for e in spans:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    root = next(e for e in spans if e["name"] == "test.root")
    child = next(e for e in spans if e["name"] == "test.child")
    # monotonic nesting: the child starts after its parent and ends
    # within it
    assert root["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= root["ts"] + root["dur"] + 1.0
    assert child["args"]["parent_id"] == root["args"]["span_id"]


def test_traces_endpoint_on_telemetry_server():
    with tr.start_span("test.root") as span:
        tid = span.trace_id
    srv = tm.serve(port=0)
    try:
        _s, body, _h = _get(srv.url + "/traces")
        assert any(t["trace_id"] == tid for t in body["recent"])
        assert body["enabled"] is True
        _s, one, _h = _get(srv.url + "/traces?id=" + tid)
        assert one["trace_id"] == tid and one["spans"]
    finally:
        srv.close()


def test_histogram_exemplar_links_worst_observation():
    h = tm.histogram("test_tracing/latency_seconds", "test")
    h.observe(0.010, trace_id="aaaa")
    h.observe(0.500, trace_id="bbbb")
    h.observe(0.020, trace_id="cccc")
    ex = tm.exemplars()
    got = ex.get("test_tracing/latency_seconds")
    assert got is not None
    assert got["trace_id"] == "bbbb"
    assert got["seconds"] == 0.5


def test_histogram_exemplar_expires_when_traffic_stops():
    """A frozen exemplar must not outlive the decay window: once traced
    observations stop (sampling off, idle service), exemplar() decays
    to None instead of pointing at a long-evicted timeline."""
    h = tm.Histogram()
    h.observe(0.5, trace_id="dddd")
    assert h.exemplar()[1] == "dddd"
    h._worst_t -= tm.EXEMPLAR_WINDOW_S + 1     # age it past the window
    assert h.exemplar() is None
    h.observe(0.1, trace_id="eeee")            # fresh traffic re-arms
    assert h.exemplar()[1] == "eeee"


# ---------------------------------------------------------------------------
# the profiler's trace and the flat span log (ISSUE 24)
# ---------------------------------------------------------------------------

TRAIN_SPANS = {"train.step", "train.forward_backward", "train.update",
               "executor.stage_input", "executor.train_step",
               "train.update_metric", "train.data_wait", "train.callbacks"}
ITERATION_SPANS = {"decode.iteration", "decode.prefill", "decode.step"}


def _host_events(trace_dir):
    """(profile_start_time ns, {name: [(start_ns, duration_ns)]}) of the
    ``/host:CPU`` plane of the one xplane under ``trace_dir``."""
    import glob
    import jax
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    origin, events = None, {}
    for plane in data.planes:
        origin = dict(plane.stats).get("profile_start_time", origin)
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.duration_ns))
    return origin, events


class _Boom(Exception):
    pass


@pytest.fixture
def profiled(tmp_path):
    """Runs the test's spans inside one ``jax.profiler`` session and
    hands back what the xplane holds of them, with ``offset``: what the
    profiler's clock (``time.time_ns``) read beyond ``perf_counter``
    while the session ran."""
    import jax
    out = {}

    def run(body):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        out["offset"] = time.time_ns() - time.perf_counter_ns()
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        out["origin"], out["events"] = _host_events(tmp_path)
        return out

    return run


@pytest.mark.parametrize("name", ["test.root", "test.child", "test.raises"])
def test_span_is_an_event_of_the_profilers_trace_on_its_clock(profiled, name):
    """A scoped span is on the xplane's host plane under its own name,
    and the log's ``t0``/``t1`` are that event's start and end to 0.2 ms
    (the file counts CLOCK_REALTIME from its ``profile_start_time``). A
    span left by an exception is closed in the trace as it is in the
    log."""
    def body():
        with pytest.raises(_Boom):
            with tr.start_span("test.root"):
                with tr.child_span("test.child"):
                    time.sleep(0.003)
                with tr.child_span("test.raises"):
                    time.sleep(0.002)
                    raise _Boom()

    got = profiled(body)
    (start_ns, dur_ns), = got["events"][name]
    rec, = [r for r in tr.span_log() if r["name"] == name]
    at = got["origin"] + start_ns - got["offset"]    # on perf_counter, ns
    assert abs(at - rec["t0"] * 1e9) < 0.2e6
    assert abs(at + dur_ns - rec["t1"] * 1e9) < 0.2e6
    assert rec["status"] == ("ok" if name == "test.child" else "error")


def test_no_annotation_is_left_open_by_an_exception(profiled):
    """After a span died of an exception the thread's next annotation is
    a sibling, not a child of a span that never closed: every event of
    the trace ends, and in the order the spans did."""
    def body():
        for _ in range(3):
            with pytest.raises(_Boom):
                with tr.start_span("test.dies"):
                    raise _Boom()
        with tr.start_span("test.after"):
            pass

    events = profiled(body)["events"]
    assert len(events["test.dies"]) == 3 and len(events["test.after"]) == 1
    ends = [s + d for s, d in sorted(events["test.dies"])]
    starts = [s for s, _d in sorted(events["test.dies"])]
    assert all(e <= s for e, s in zip(ends, starts[1:]))
    assert ends[-1] <= events["test.after"][0][0]


def test_span_log_is_flat_outlives_the_ring_and_is_cleared_by_reset():
    """One log for every trace: it outlives the ring's rotation, holds
    the last ``_LOG_SPANS`` spans and ``reset()`` empties it."""
    assert tr._log.maxlen == tr._LOG_SPANS == 16384
    n = tr._ring.maxlen + 6
    for i in range(n):
        with tr.start_span("test.root", attrs={"i": i}):
            with tr.child_span("test.child"):
                pass
    log = tr.span_log()
    assert len(tr.finished_traces()) == tr._ring.maxlen < n
    assert [r["attrs"]["i"] for r in log if r["name"] == "test.root"] \
        == list(range(n))
    assert len(log) == 2 * n
    roots = dict((r["span_id"], r) for r in log if r["name"] == "test.root")
    assert all(r["parent_id"] in roots
               for r in log if r["name"] == "test.child")
    # the record IS the ring's dict: no second allocation
    newest = tr.finished_traces()[0]
    assert any(r is s for r in log for s in newest["spans"])
    tr.reset()
    assert tr.span_log() == []


def test_span_log_is_bounded(monkeypatch):
    from collections import deque
    monkeypatch.setattr(tr, "_log", deque(maxlen=8))
    for _ in range(20):
        with tr.start_span("test.root"):
            pass
    assert len(tr.span_log()) == 8


def test_recorded_interval_reaches_the_log(profiled):
    """``record_span`` (an interval seen after the fact) can have no
    annotation, but the log holds it with the ends it was given."""
    def body():
        with tr.start_span("test.root") as span:
            t0 = time.perf_counter()
            tr.record_span("test.late", span.ctx, t0, t0 + 0.002)

    events = profiled(body)["events"]
    rec, = [r for r in tr.span_log() if r["name"] == "test.late"]
    assert rec["t1"] - rec["t0"] == pytest.approx(0.002)
    assert "test.root" in events and "test.late" not in events


def test_disabled_logs_nothing():
    """``MXNET_TRACING=0`` (``enable(False)``) turns the log and the
    annotations off with everything else."""
    tr.enable(False)
    with tr.start_span("test.root"):
        with tr.child_span("test.child"):
            pass
    tr.enable(True)
    assert tr.span_log() == []
    tr.set_sample(0.0)
    with tr.start_span("test.root"):
        pass
    assert tr.span_log() == []


def test_fit_step_logs_the_eight_training_spans_under_train_step():
    rng = np.random.RandomState(0)
    it = io.NDArrayIter(rng.rand(80, 16).astype(np.float32),
                        rng.randint(0, 8, size=(80,)).astype(np.float32),
                        batch_size=20)
    seen = []
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1),),
            batch_end_callback=lambda p: seen.append(p.nbatch))
    log = tr.span_log()
    roots = [r for r in log if r["name"] == "train.step"]
    assert [r["attrs"]["nbatch"] for r in roots] == seen == [0, 1, 2, 3]
    # a warm step that has a successor: nothing compiles, and the next
    # batch is fetched and pre-staged in it (the epoch's last has none)
    last = roots[-2]
    step = [r for r in log if r["trace_id"] == last["trace_id"]]
    assert sorted(r["name"] for r in step) \
        == sorted(list(TRAIN_SPANS) + ["executor.stage_input"])
    by_id = dict((r["span_id"], r) for r in step)
    for r in step:
        up = r
        while up["parent_id"] is not None:
            up = by_id[up["parent_id"]]
        assert up is last
        # children lie inside the root: they tile the step
        assert last["t0"] <= r["t0"] and r["t1"] <= last["t1"]
    # the deferred fused step runs under the update: bind what the step
    # before placed ahead, then call
    bound, ahead = sorted((r for r in step
                           if r["name"] == "executor.stage_input"),
                          key=lambda r: r["t0"])
    call, = [r for r in step if r["name"] == "executor.train_step"]
    update, = [r for r in step if r["name"] == "train.update"]
    assert bound["parent_id"] == call["parent_id"] == update["span_id"]
    assert bound["attrs"] == {"prestaged": True}
    assert bound["t1"] <= call["t0"]
    # the put for the NEXT step lies behind this step's call, under the
    # same root, after the data wait and before the metric's sync
    wait, = [r for r in step if r["name"] == "train.data_wait"]
    metric, = [r for r in step if r["name"] == "train.update_metric"]
    assert ahead["parent_id"] == last["span_id"]
    assert ahead["attrs"] == {"ahead": True}
    assert call["t1"] <= update["t1"] <= wait["t0"]
    assert wait["t1"] <= ahead["t0"] and ahead["t1"] <= metric["t0"]
    # the root covers the callbacks, and there is exactly one span of them
    cb, = [r for r in step if r["name"] == "train.callbacks"]
    assert metric["t1"] <= cb["t0"] and cb["t1"] <= last["t1"]
    # the epoch's last step stages nothing ahead; its first bound what
    # was prepared where the batch was fetched, outside any step
    final = [r for r in log if r["trace_id"] == roots[-1]["trace_id"]]
    assert sorted(r["name"] for r in final) == sorted(TRAIN_SPANS)
    first, = [r for r in log if r["trace_id"] == roots[0]["trace_id"]
              and r["name"] == "executor.stage_input"
              and "prestaged" in r["attrs"]]
    assert first["attrs"]["prestaged"] is True


def test_callback_exception_closes_step_and_callbacks_spans():
    """The benchmark ends ``fit`` by raising out of a callback."""
    rng = np.random.RandomState(0)
    it = io.NDArrayIter(rng.rand(40, 16).astype(np.float32),
                        np.zeros(40, np.float32), batch_size=20)

    def stop(_param):
        raise _Boom()

    mod = Module(_mlp_sym(), context=mx.cpu())
    with pytest.raises(_Boom):
        mod.fit(it, num_epoch=1, optimizer="sgd", batch_end_callback=stop)
    assert tr.current() is None
    ends = dict((r["name"], r) for r in tr.span_log())
    assert ends["train.callbacks"]["status"] == "error"
    assert ends["train.step"]["status"] == "error"
    assert ends["train.update_metric"]["status"] == "ok"


def _tiny_decode_engine():
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import (TransformerConfig,
                                                init_transformer_params)
    from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=64)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=0)
    dcfg = DecodeConfig(slots=4, page_size=4, num_pages=64, max_context=48,
                        max_new_tokens=8, queue_depth=16,
                        default_timeout_ms=60000)
    return DecodeEngine(params, cfg, dcfg).start().warmup()


def test_decode_loop_logs_its_iterations_without_a_caller_context():
    """``submit()`` with no context: the per-request spans record
    nothing, the engine's own loop still logs one ``decode.iteration``
    per pass with its prefills and its step, and ``context_tokens`` is
    the positions the step attends over."""
    eng = _tiny_decode_engine()
    tr.reset()                          # the warm-up's compile spans
    try:
        first = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        assert first.tctx is None
        first.result()
        alone = len(tr.span_log())
        pair = [eng.submit([7, 8, 9], max_new_tokens=3),
                eng.submit([5, 6], max_new_tokens=3)]
        for sess in pair:
            sess.result()
    finally:
        eng.close(drain=False)
    log = tr.span_log()
    assert set(r["name"] for r in log) == ITERATION_SPANS
    its = [r for r in log if r["name"] == "decode.iteration"]
    ids = set(r["span_id"] for r in its)
    assert all(r["parent_id"] is None for r in its)
    assert all(r["parent_id"] in ids for r in log if r not in its)
    # the engine's passes are in the log, not in the ring of traces
    assert tr.finished_traces() == []

    def kids(it, name):
        return [r for r in log
                if r["parent_id"] == it["span_id"] and r["name"] == name]

    # the first request alone: prompt of 5, so its three decode steps
    # attend over 6, 7 and 8 positions (the new token's own included)
    steps = [r for r in log[:alone] if r["name"] == "decode.step"]
    # (a model without window layers sees the same under a window, and
    # a dense one carries none of the expert layer's moe_* stamps)
    assert [s["attrs"] for s in steps] == [
        {"context_tokens": n, "window_context_tokens": n}
        for n in (6, 7, 8)]
    assert len(kids(its[0], "decode.prefill")) == 1
    assert its[0]["attrs"] == {"live": 0}
    # a pass's prefills and its step lie inside it, in that order
    for it in its:
        order = kids(it, "decode.prefill") + kids(it, "decode.step")
        assert order and len(kids(it, "decode.step")) <= 1
        assert all(a["t1"] <= b["t0"] for a, b in zip(order, order[1:]))
        assert it["t0"] <= order[0]["t0"] and order[-1]["t1"] <= it["t1"]
    # two sequences in one step: both contexts are counted (prompts of 3
    # and 2: the first step over both attends over 4 + 3 positions)
    later = [r["attrs"]["context_tokens"] for r in log[alone:]
             if r["name"] == "decode.step"]
    assert 7 in later
    # sequences carried into a pass are its ``live``
    assert any(it["attrs"]["live"] > 0 for it in its)


def test_engine_passes_do_not_turn_the_request_ring_over():
    """A request that came with a context keeps its trace in the ring
    (``get_trace`` by its id, ``/traces``' recent list) however many
    passes the engine makes after it, at the default ring size: a pass
    goes to the span log alone. A pass that dies of an error is kept
    among the slow exemplars like any trace."""
    assert tr._ring.maxlen == 64
    eng = _tiny_decode_engine()
    tr.reset()
    try:
        with tr.start_span("http.request", trace_id="req-1") as span:
            eng.submit([1, 2, 3], max_new_tokens=3,
                       ctx=span.ctx).result()
        for _ in range(20):
            eng.submit([4, 5, 6, 7], max_new_tokens=8).result()
    finally:
        eng.close(drain=False)
    passes = [r for r in tr.span_log() if r["name"] == "decode.iteration"]
    assert len(passes) > 100
    kept = tr.get_trace("req-1")
    assert kept is not None and kept["root"] == "http.request"
    assert {"decode.schedule", "decode.prefill", "decode.step"} \
        <= set(kept["phases"])
    recent = tr.traces_payload()["recent"]
    assert [t["trace_id"] for t in recent] == ["req-1"]
    assert tr.slow_traces() == []
    with pytest.raises(_Boom):
        with tr.start_span("decode.iteration", ring=False):
            raise _Boom()
    assert [t["root"] for t in tr.slow_traces()] == ["decode.iteration"]
    assert [t["trace_id"] for t in tr.finished_traces()] == ["req-1"]


# ---------------------------------------------------------------------------
# log correlation
# ---------------------------------------------------------------------------

def test_log_plain_suffix_and_json_mode():
    from mxnet_tpu.log import JsonFormatter, TraceFormatter
    rec = logging.LogRecord("t", logging.INFO, __file__, 1,
                            "hello %s", ("world",), None)
    plain = TraceFormatter("%(levelname)s %(name)s: %(message)s")
    jsonf = JsonFormatter()
    # outside any context: no suffix, no trace fields
    assert "[trace=" not in plain.format(rec)
    assert "trace_id" not in json.loads(jsonf.format(rec))
    with tr.start_span("test.root") as span:
        line = plain.format(rec)
        assert "[trace=%s" % span.trace_id in line
        obj = json.loads(jsonf.format(rec))
        assert obj["trace_id"] == span.trace_id
        assert obj["span_id"] == span.span_id
        assert obj["msg"] == "hello world"
        assert obj["level"] == "INFO"


def test_get_logger_json_mode(monkeypatch, capsys):
    monkeypatch.setenv("MXNET_LOG_JSON", "1")
    from mxnet_tpu.log import get_logger
    logger = get_logger("test_tracing_json_logger", level=logging.INFO)
    with tr.start_span("test.root") as span:
        logger.info("traced message")
    err = capsys.readouterr().err.strip().splitlines()[-1]
    obj = json.loads(err)
    assert obj["msg"] == "traced message"
    assert obj["trace_id"] == span.trace_id


# ---------------------------------------------------------------------------
# diagnostics + docs drift + overhead
# ---------------------------------------------------------------------------

def test_diagnostics_slow_traces_and_serve_status(tmp_path):
    tr.set_slow_ms(0)
    with tr.start_span("test.root"):
        pass
    eng = _engine(tmp_path).start()
    eng.warmup()
    try:
        info = mx.diagnostics(as_dict=True)
        assert info["tracing_enabled"] is True
        assert info["recent_slow_traces"]
        row = info["recent_slow_traces"][0]
        assert set(row) >= {"trace_id", "root", "duration_ms", "phases"}
        assert "serve_engines" in info
        # other tests' closed-but-not-yet-GC'd engines are filtered out;
        # ours is the one ready row
        ready = [r for r in info["serve_engines"] if r["ready"]]
        assert len(ready) == 1
        eng_row = ready[0]
        assert eng_row["workers_alive"] >= 1
        assert eng_row["queue_depth"] == 0
    finally:
        eng.close(drain=False)


def test_metrics_docs_in_sync():
    """tools/check_metrics_docs.py: every registered metric/span name
    literal is documented, and nothing documented is stale."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_metrics_docs.py")
    spec = importlib.util.spec_from_file_location("check_metrics_docs",
                                                  path)
    modl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(modl)
    drift = modl.check()
    assert all(not v for v in drift.values()), drift


def test_dispatch_overhead_sampling0():
    """The sampling-0 path (tracing enabled, nothing recording) stays
    close to the disabled path on the dispatch microbench. Asserted
    loosely (CI wall-clock drifts more than the effect)."""
    x = mx.nd.array(np.random.rand(16, 16).astype("float32"))
    mx.nd.dot(x, x).wait_to_read()

    def chunk(on, iters=200):
        tr.enable(on)
        tr.set_sample(0.0)
        t0 = time.perf_counter()
        for _ in range(iters):
            mx.nd.dot(x, x)
        return time.perf_counter() - t0

    chunk(True)
    chunk(False)
    on, off = float("inf"), float("inf")
    for _ in range(6):
        on = min(on, chunk(True))
        off = min(off, chunk(False))
    tr.enable(True)
    tr.set_sample(1.0)
    assert on <= off * 1.5 + 1e-3, \
        "sampling-0 tracing overhead too high: on=%.4fs off=%.4fs" \
        % (on, off)
