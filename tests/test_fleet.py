"""Serving fleet tier (ISSUE 18): prefix-affinity router + SLO-driven
replica autoscaler.

Unit layers run against fake in-process HTTP backends (no jax in the
loop) so router policy — consistent-hash affinity, yield-to-load,
least-outstanding, ejection + retry — is asserted cheaply; the
acceptance test drives a REAL fleet of serve_http worker subprocesses
through a load ramp, a SIGKILL under traffic, and a drain-retirement,
asserting replica count tracks load, only in-flight requests can be
lost, zero XLA compiles happen after warmup on every replica
(including the warmset-spawned mid-ramp one), and the flight recorder
tells the story post-mortem.
"""
import http.client
import http.server
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import blackbox as bb
from mxnet_tpu import fault
from mxnet_tpu import health
from mxnet_tpu import telemetry as tm
from mxnet_tpu import tracing as tr
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import ProcessSupervisor, TrainingSupervisor
from mxnet_tpu.serve import (Fleet, ModelRegistry, NoLiveReplicaError,
                             Router, serve_http, serve_router)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _post(url, path, payload, timeout=30, headers=()):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers=dict({"Content-Type": "application/json"}, **dict(headers)),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}"), dict(e.headers)


def _get(url, path, timeout=10):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return r.status, r.read()


class _EchoHandler(http.server.BaseHTTPRequestHandler):
    """Fake replica: echoes the propagation headers back as JSON."""
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        hold = getattr(self.server, "hold_s", 0.0)
        if hold:
            time.sleep(hold)
        out = json.dumps(
            {"port": self.server.server_address[1],
             "rid": self.headers.get("X-Request-Id"),
             "deadline_ms": self.headers.get("X-Deadline-Ms"),
             "trace_ctx": self.headers.get("X-Trace-Context")}
        ).encode() + b"\n"
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *args):
        pass


def _fake_backend():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _EchoHandler)
    srv.daemon_threads = True
    threading.Thread(target=lambda: srv.serve_forever(poll_interval=0.05),
                     daemon=True).start()
    return srv


@pytest.fixture
def two_backends():
    b1, b2 = _fake_backend(), _fake_backend()
    yield b1, b2
    for b in (b1, b2):
        b.shutdown()
        b.server_close()


# ---------------------------------------------------------------------------
# satellite 1: ProcessSupervisor extraction
# ---------------------------------------------------------------------------


def test_process_supervisor_triage_policy():
    """Preemption-grade exits always relaunch and reset the budget;
    genuine failures burn it; the relaunch metric keeps its labels."""
    tm.reset()
    ps = ProcessSupervisor(max_failures=2, relaunch_delay_s=0)
    assert ps.triage(-9) == ("preempt", True)       # signal death
    assert ps.triage(137) == ("preempt", True)      # 128+SIGKILL
    assert ps.triage(143) == ("preempt", True)      # 128+SIGTERM
    assert ps.failures == 0
    assert ps.triage(1) == ("failure", True)
    assert ps.triage(137) == ("preempt", True)      # resets the count
    assert ps.failures == 0
    assert ps.triage(1) == ("failure", True)
    assert ps.triage(2) == ("failure", False)       # budget exhausted
    text = tm.render_prometheus()
    assert 'mxnet_supervisor_relaunches_total{reason="preempt"} 4' in text
    # two failure relaunches; the exhausted decision does NOT count
    assert 'mxnet_supervisor_relaunches_total{reason="failure"} 2' in text


def test_process_supervisor_note_success_resets_budget():
    ps = ProcessSupervisor(max_failures=2, relaunch_delay_s=0)
    assert ps.triage(1) == ("failure", True)
    ps.note_success()
    assert ps.failures == 0
    assert ps.triage(1) == ("failure", True)        # budget is fresh


def test_training_supervisor_delegates_behavior_identical(tmp_path):
    """Regression: the old entry point still returns 0 on clean exit
    and the last rc after max_failures genuine failures, and still
    reads MXNET_SUPERVISOR_MAX_FAILURES by default."""
    assert TrainingSupervisor._PREEMPT_RCS == frozenset((137, 143))
    assert TrainingSupervisor.is_preemption_rc(-15)
    assert not TrainingSupervisor.is_preemption_rc(7)
    runs = tmp_path / "runs.txt"
    script = tmp_path / "job.py"
    script.write_text(
        "import sys\n"
        "with open(%r, 'a') as f: f.write('x')\n"
        "sys.exit(7)\n" % str(runs))
    rc = TrainingSupervisor.supervise(
        [sys.executable, str(script)], max_failures=2,
        relaunch_delay_s=0)
    assert rc == 7
    assert runs.read_text() == "xx"                 # ran exactly twice
    script.write_text("raise SystemExit(0)\n")
    assert TrainingSupervisor.supervise(
        [sys.executable, str(script)], max_failures=1,
        relaunch_delay_s=0) == 0


# ---------------------------------------------------------------------------
# satellite 3: machine-readable /alerts
# ---------------------------------------------------------------------------


def _check_alerts_payloads(url):
    status, body = _get(url, "/alerts")
    human = json.loads(body)
    assert status == 200
    # the default (human/dashboard) payload is unchanged
    assert set(human) == {"rules", "firing", "interval_s",
                          "evaluator_alive"}
    assert all("description" in r for r in human["rules"])
    status, body = _get(url, "/alerts?format=json")
    machine = json.loads(body)
    assert status == 200
    assert machine["format"] == "json"
    assert isinstance(machine["firing"], list)
    by_name = {r["rule"]: r for r in machine["rules"]}
    assert "serve_p99" in by_name
    row = by_name["serve_p99"]
    assert row["state"] in ("ok", "firing")
    assert len(row["windows"]) == 2
    assert all({"window_s", "burn_frac"} <= set(w)
               for w in row["windows"])


def test_alerts_format_json_telemetry_mount():
    health.reset()
    srv = tm.serve(port=0)
    try:
        _check_alerts_payloads("http://127.0.0.1:%d" % srv.port)
    finally:
        srv.close()
        health.reset()


# ---------------------------------------------------------------------------
# router policy units (fake backends; no jax in the loop)
# ---------------------------------------------------------------------------


def test_affinity_key_prefix_head():
    r = Router(prefix_tokens=4, affinity_slack=2)
    body = json.dumps({"prompt": [1, 2, 3, 4, 5, 6]}).encode()
    assert r.affinity_key("/generate", body) == "1,2,3,4"
    # same head, different tail -> same key (one prefix family)
    body2 = json.dumps({"prompt": [1, 2, 3, 4, 99, 98]}).encode()
    assert r.affinity_key("/generate", body2) == "1,2,3,4"
    assert r.affinity_key("/predict", body) is None
    assert r.affinity_key("/generate", b"not json") is None
    assert r.affinity_key("/generate", json.dumps([7, 8]).encode()) \
        == "7,8"


def test_affinity_pins_and_yields_to_load():
    tm.reset()
    r = Router(prefix_tokens=4, affinity_slack=2)
    r.add("a", "127.0.0.1", 1001)
    r.add("b", "127.0.0.1", 1002)
    key = r.affinity_key("/generate",
                         json.dumps({"prompt": [5, 5, 5, 5, 1]}).encode())
    rep, hit = r.pick(key)
    assert hit
    pinned = rep.name
    r._release(rep)
    # stable: the same key pins the same replica across picks
    for _ in range(3):
        rep, hit = r.pick(key)
        assert (rep.name, hit) == (pinned, True)
        r._release(rep)
    # saturate the pinned replica past the slack: affinity yields
    with r._lock:
        r._replicas[pinned].outstanding = 5
    rep, hit = r.pick(key)
    assert rep.name != pinned and not hit
    text = tm.render_prometheus()
    assert "mxnet_router_affinity_yields_total 1" in text
    assert "mxnet_router_affinity_hits_total 4" in text


def test_least_outstanding_pick():
    r = Router()
    r.add("a", "127.0.0.1", 1001)
    r.add("b", "127.0.0.1", 1002)
    with r._lock:
        r._replicas["a"].outstanding = 3
    rep, hit = r.pick()
    assert (rep.name, hit) == ("b", False)
    with pytest.raises(NoLiveReplicaError):
        r.pick(exclude={"a", "b"})


def test_router_ejects_dead_replica_and_retries(two_backends):
    b1, b2 = two_backends
    r = Router(forward_retries=2)
    r.add("a", "127.0.0.1", b1.server_address[1])
    r.add("b", "127.0.0.1", b2.server_address[1])
    with serve_router(r, port=0) as front:
        b1.shutdown()
        b1.server_close()
        live_port = b2.server_address[1]
        for _ in range(4):
            status, out, _ = _post(front.url, "/predict", {"inputs": 1})
            assert status == 200 and out["port"] == live_port
        snap = {x["name"]: x for x in r.replicas()}
        assert not snap["a"]["healthy"] and snap["b"]["healthy"]
        # everything dead -> 503 with Retry-After, not a hang
        r.eject("b")
        status, out, headers = _post(front.url, "/predict", {"inputs": 1})
        assert status == 503 and "Retry-After" in headers


def test_router_forward_fault_point(two_backends):
    """An armed router.forward fault looks exactly like a vanished
    replica: eject + retry onto the next one, request still succeeds."""
    b1, b2 = two_backends
    tm.reset()
    r = Router(forward_retries=2)
    r.add("a", "127.0.0.1", b1.server_address[1])
    r.add("b", "127.0.0.1", b2.server_address[1])
    with serve_router(r, port=0) as front:
        with fault.arming("router.forward", step=1, kind="raise"):
            status, out, _ = _post(front.url, "/predict", {"inputs": 1})
        assert status == 200
        assert fault.hits("router.forward") >= 1
        assert sum(1 for x in r.replicas() if x["healthy"]) == 1
    text = tm.render_prometheus()
    assert "mxnet_router_forward_retries_total 1" in text


def test_router_deadline_expiry_and_propagation(two_backends):
    b1, _ = two_backends
    r = Router()
    r.add("a", "127.0.0.1", b1.server_address[1])
    with serve_router(r, port=0) as front:
        # a microscopic budget dies in the router with a 504
        status, out, _ = _post(front.url, "/predict",
                               {"inputs": 1, "timeout_ms": 1e-6})
        assert status == 504
        # a real budget is forwarded as the REMAINING deadline
        status, out, headers = _post(
            front.url, "/predict", {"inputs": 1, "timeout_ms": 5000},
            headers={"X-Request-Id": "fleet-rid-1"})
        assert status == 200
        assert out["rid"] == "fleet-rid-1"
        assert headers["X-Request-Id"] == "fleet-rid-1"
        assert 0 < float(out["deadline_ms"]) <= 5000
        wire = json.loads(out["trace_ctx"])
        assert wire["trace_id"] == "fleet-rid-1" and wire["sampled"]


# ---------------------------------------------------------------------------
# satellite 2 + 3: end-to-end against a REAL replica (in-process)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def real_replica(tmp_path_factory):
    """One warmed serve_http replica over a tiny FC+softmax model."""
    tmp = tmp_path_factory.mktemp("fleet_model")
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(0)
    path = str(tmp / "m.params")
    mx.nd.save(path, {
        "arg:fc_weight": mx.nd.array(rng.randn(3, 4).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(rng.randn(3).astype(np.float32))})
    with open(path, "rb") as f:
        blob = f.read()
    reg = ModelRegistry(sym.tojson(), blob, input_shapes={"data": (1, 4)})
    reg.warmup()
    srv = serve_http(reg, port=0)
    yield srv
    srv.close()
    reg.close()
    health.reset()


def test_alerts_format_json_serve_mount(real_replica):
    _check_alerts_payloads(real_replica.url)


def test_end_to_end_trace_links_router_and_replica_spans(real_replica):
    """One trace on the ROUTER's /traces holds the whole story:
    router.request -> router.forward -> the replica's http.request and
    its serve.* children, clock-rebased into the router's timeline."""
    r = Router()
    r.add("a", "127.0.0.1", real_replica.port)
    rid = "fleet-e2e-trace-1"
    with serve_router(r, port=0) as front:
        status, out, _ = _post(
            front.url, "/predict",
            {"inputs": {"data": [[1.0, 2.0, 3.0, 4.0]]},
             "timeout_ms": 20000},
            headers={"X-Request-Id": rid})
        assert status == 200 and out["rows"] == 1
        code, body = _get(front.url, "/traces?trace_id=" + rid)
        assert code == 200
    trace = tr.get_trace(rid)
    assert trace is not None
    spans = {s["name"]: s for s in trace["spans"]}
    assert {"router.request", "router.forward",
            "http.request"} <= set(spans)
    root = spans["router.request"]
    fwd = spans["router.forward"]
    rep = spans["http.request"]
    assert fwd["parent_id"] == root["span_id"]
    assert rep["parent_id"] == fwd["span_id"]          # cross-process link
    assert root["t0"] <= fwd["t0"] <= rep["t0"]        # rebased clock nests
    assert "serve.compute" in trace["phases"]          # replica internals


def test_replica_honors_router_deadline_header(real_replica):
    """X-Deadline-Ms caps the replica-side budget even when the body
    asks for more — replica 504 accounting matches the router's view."""
    conn = http.client.HTTPConnection("127.0.0.1", real_replica.port,
                                      timeout=30)
    try:
        body = json.dumps({"inputs": {"data": [[1, 2, 3, 4]]},
                           "timeout_ms": 60000}).encode()
        conn.request("POST", "/predict", body,
                     {"Content-Type": "application/json",
                      "X-Deadline-Ms": "0.0"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 504, out
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# autoscaler hysteresis (no subprocesses: stubbed spawn/retire)
# ---------------------------------------------------------------------------


def test_autoscaler_hysteresis(tmp_path, monkeypatch):
    from mxnet_tpu.serve.fleet import _Replica

    class _FakeProc(object):
        pid = 0

    sigs = {"rows": []}
    fleet = Fleet({"builder": "x:y"}, str(tmp_path / "wd"),
                  min_replicas=1, max_replicas=3, scale_up_s=10.0,
                  scale_down_s=30.0, cooldown_s=15.0,
                  signals_fn=lambda: sigs["rows"])
    actions = []
    monkeypatch.setattr(fleet, "_spawn",
                        lambda reason: actions.append(("up", reason)))
    monkeypatch.setattr(
        fleet, "_retire",
        lambda name, reason: actions.append(("down", name, reason)))
    # seed two fake live replicas so scale-down has a "newest" to pick
    for name, spawned in (("r1", 1.0), ("r2", 2.0)):
        rep = _Replica(name, _FakeProc(), None)
        rep.spawned_t = spawned
        fleet._replicas[name] = rep
    hot = [{"name": "r1", "firing": ["serve_p99"], "queue_depth": 0.0}]
    idle = [{"name": "r1", "firing": [], "queue_depth": 0.0}]
    busy_q = [{"name": "r1", "firing": [], "queue_depth": 9.0}]

    # a burn blip shorter than the hold window never scales
    sigs["rows"] = hot
    assert fleet._autoscale(now=0.0) is None
    sigs["rows"] = idle
    assert fleet._autoscale(now=5.0) is None
    assert fleet.target == 1 and not actions

    # sustained burn scales up once the hold window elapses
    sigs["rows"] = hot
    assert fleet._autoscale(now=10.0) is None
    assert fleet._autoscale(now=21.0) == "up"
    assert fleet.target == 2 and actions[-1][0] == "up"
    assert "burn" in actions[-1][1]

    # cooldown gates an immediate second decision, even under burn
    assert fleet._autoscale(now=22.0) is None
    sigs["rows"] = idle
    assert fleet._autoscale(now=30.0) is None          # hot streak resets

    # queue growth alone (no burn rule firing) also counts as hot
    sigs["rows"] = busy_q
    assert fleet._autoscale(now=40.0) is None
    assert fleet._autoscale(now=51.0) == "up"
    assert fleet.target == 3

    # slack must be sustained for the LONGER window to scale down,
    # and it retires the NEWEST replica
    sigs["rows"] = idle
    assert fleet._autoscale(now=70.0) is None
    assert fleet._autoscale(now=90.0) is None          # 20s < 30s hold
    assert fleet._autoscale(now=100.5) == "down"
    assert fleet.target == 2 and actions[-1] == ("down", "r2", "slack")

    # never below min_replicas
    fleet.target = 1
    sigs["rows"] = idle
    fleet._cold_since = None
    fleet._last_scale = None
    assert fleet._autoscale(now=200.0) is None
    assert fleet._autoscale(now=231.0) is None
    assert fleet.target == 1

    # training-side rules must not scale the serving fleet
    sigs["rows"] = [{"name": "r1", "firing": ["numerics"],
                     "queue_depth": 0.0}]
    fleet.target = 1
    fleet._last_scale = None
    assert fleet._autoscale(now=300.0) is None
    assert fleet._autoscale(now=311.0) is None
    assert fleet.target == 1


# ---------------------------------------------------------------------------
# subprocess fleet: chaos + acceptance
# ---------------------------------------------------------------------------

_BUILDER_SRC = """\
import os
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.serve import ModelRegistry

def build(spec):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(0)
    path = os.path.join(spec["workdir"], "m-%d.params" % os.getpid())
    mx.nd.save(path, {
        "arg:fc_weight": mx.nd.array(rng.randn(3, 4).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(rng.randn(3).astype(np.float32))})
    with open(path, "rb") as f:
        blob = f.read()
    reg = ModelRegistry(sym.tojson(), blob, input_shapes={"data": (1, 4)})
    reg.warmup()
    return reg
"""


def _write_spec(tmp_path, extra_env=None):
    (tmp_path / "fleet_test_builder.py").write_text(_BUILDER_SRC)
    env = {"JAX_PLATFORMS": "cpu"}
    env.update(extra_env or {})
    return {"builder": "fleet_test_builder:build",
            "pythonpath": [str(tmp_path), REPO_ROOT],
            "workdir": str(tmp_path),
            "env": env}


def _scrape_counter(port, prom_name):
    """Unlabelled counter value from a replica's /metrics, or 0.0."""
    _, body = _get("http://127.0.0.1:%d" % port, "/metrics")
    for line in body.decode().splitlines():
        if line.startswith(prom_name + " "):
            return float(line.split()[-1])
    return 0.0


def _predict_until(stop, front, results):
    """One client: /predict after /predict through the router's front
    until ``stop``; appends (status, seconds), -1 for a refused
    connection."""
    while not stop.is_set():
        t0 = time.perf_counter()
        try:
            status, _, _ = _post(
                front.url, "/predict",
                {"inputs": {"data": [[1.0, 2.0, 3.0, 4.0]]},
                 "timeout_ms": 20000}, timeout=30)
        except (OSError, urllib.error.URLError):
            status = -1
        results.append((status, time.perf_counter() - t0))
        time.sleep(0.02)


def _bank_compile_baselines(fleet, baselines):
    """Each serving replica's compile counter, read once, the first
    time it is seen up (a replica is up only after its warmup)."""
    for rep in fleet.status()["replicas"]:
        if rep["port"] and rep["name"] not in baselines:
            baselines[rep["name"]] = (
                rep["port"],
                _scrape_counter(rep["port"],
                                "mxnet_jit_backend_compile_total"))


def _assert_no_replica_compiled(fleet, baselines):
    """Zero XLA compiles after warmup on EVERY replica that is still
    up; one spawned off the warm-set manifest rode the disk cache."""
    up = {r["name"] for r in fleet.status()["replicas"]}
    for name, (port, base) in baselines.items():
        if name not in up:
            continue                               # killed/retired
        now_count = _scrape_counter(
            port, "mxnet_jit_backend_compile_total")
        assert now_count == base, (name, base, now_count)
        if name != "r1":
            assert _scrape_counter(
                port, "mxnet_programs_disk_hits_total") > 0


def test_fleet_scales_up_and_no_replica_compiles(tmp_path):
    """The acceptance's compile gate, in tier-1: a REAL fleet of worker
    subprocesses scales 1 -> 2 under live traffic, the second replica
    spawning warm off the first's warm-set manifest, and no replica
    compiles anything after its warmup."""
    cache = tmp_path / "cache"
    cache.mkdir()
    spec = _write_spec(
        tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    sigs = {"rows": []}
    fleet = Fleet(spec, str(tmp_path / "wd"), min_replicas=1,
                  max_replicas=2, interval_s=0.15, scale_up_s=0.4,
                  scale_down_s=30.0, cooldown_s=0.6,
                  spawn_timeout_s=120, drain_timeout_s=30,
                  signals_fn=lambda: sigs["rows"])
    results, baselines = [], {}
    stop = threading.Event()
    try:
        fleet.start()
        front = serve_router(fleet.router, port=0)
        _bank_compile_baselines(fleet, baselines)
        clients = [threading.Thread(target=_predict_until,
                                    args=(stop, front, results),
                                    daemon=True) for _ in range(2)]
        for t in clients:
            t.start()
        sigs["rows"] = [{"name": "r1", "firing": ["serve_p99"],
                         "queue_depth": 0.0}]
        deadline = time.time() + 60
        while time.time() < deadline:
            st = fleet.status()
            if st["live"] == 2 and all(r["spawn_s"]
                                       for r in st["replicas"]):
                break                            # the second one SERVES
            time.sleep(0.1)
        assert st["live"] == 2 and fleet.target == 2, st
        assert [r for r in st["replicas"] if r["name"] != "r1"][0]["warm"]
        _bank_compile_baselines(fleet, baselines)
        sigs["rows"] = []
        served = len(results)
        deadline = time.time() + 30              # traffic over BOTH
        while time.time() < deadline and len(results) < served + 20:
            time.sleep(0.05)
        stop.set()
        for t in clients:
            t.join(timeout=30)
        assert len(baselines) == 2
        assert results and all(s == 200 for s, _ in results), results
        for port, _base in baselines.values():     # both replicas served
            assert _scrape_counter(port, "mxnet_serving_requests_total") > 0
        _assert_no_replica_compiled(fleet, baselines)
        front.close()
    finally:
        stop.set()
        fleet.close()


@pytest.mark.slow
def test_worker_fault_point_flight_recorder_names_killer(tmp_path):
    """A fleet.replica crash fault SIGKILLs the worker mid-serve; its
    own flight ring's last fault record names the killer, and the exit
    code triages as preemption-grade."""
    spec = _write_spec(tmp_path)
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    ready = tmp_path / "w.ready.json"
    ring = str(tmp_path / "w.flight.bin")
    env = dict(os.environ)
    env.update(spec["env"])
    env["PYTHONPATH"] = os.pathsep.join([str(tmp_path), REPO_ROOT])
    env["MXNET_FAULT_INJECT"] = "fleet.replica:3:crash"
    env["MXNET_FLIGHT_RECORDER"] = ring
    proc = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.serve.fleet", "--worker",
         "--spec", str(spec_path), "--ready-file", str(ready),
         "--name", "chaos"],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 137
    assert ProcessSupervisor.is_preemption_rc(rc)
    assert ready.exists()                      # it WAS serving first
    events, _torn = bb.read_events(ring)
    faults = [e for e in events if e["event"] == "fault"]
    assert faults and faults[-1]["point"] == "fleet.replica"
    assert faults[-1]["kind"] == "crash"


@pytest.mark.slow
def test_fleet_acceptance_ramp_kill_drain(tmp_path):
    """The tentpole, end to end on real subprocesses: load ramp scales
    1->2 (the mid-ramp replica spawning warm off the shared warmset
    manifest), a SIGKILL under traffic loses only in-flight requests
    and the fleet re-converges with zero operator action, slack drains
    a replica with zero in-flight lost, zero XLA compiles happen after
    warmup on every replica, and the parent flight ring tells the
    story (replica_death -> scale_up)."""
    cache = tmp_path / "cache"
    cache.mkdir()
    bb.reset()
    bb.configure(str(tmp_path / "parent.flight.bin"))
    spec = _write_spec(
        tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    sigs = {"rows": []}
    fleet = Fleet(spec, str(tmp_path / "wd"), min_replicas=1,
                  max_replicas=2, interval_s=0.15, scale_up_s=0.4,
                  scale_down_s=0.8, cooldown_s=0.6,
                  spawn_timeout_s=120, drain_timeout_s=30,
                  signals_fn=lambda: sigs["rows"])
    results = []
    stop = threading.Event()

    try:
        fleet.start()
        front = serve_router(fleet.router, port=0)
        baselines = {}
        _bank_compile_baselines(fleet, baselines)
        threads = [threading.Thread(target=_predict_until,
                                    args=(stop, front, results),
                                    daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()

        # ---- ramp: sustained burn scales 1 -> 2, warm off the manifest
        assert (cache / "warmset.json").exists()   # replica 1 wrote it
        sigs["rows"] = [{"name": "r1", "firing": ["serve_p99"],
                         "queue_depth": 0.0}]
        deadline = time.time() + 60
        while time.time() < deadline and fleet.live_count() < 2:
            time.sleep(0.1)
        st = fleet.status()
        assert st["live"] == 2 and fleet.target == 2, st
        mid_ramp = [r for r in st["replicas"] if r["name"] != "r1"][0]
        assert mid_ramp["warm"], st                # manifest was present
        _bank_compile_baselines(fleet, baselines)
        sigs["rows"] = []                          # hold (hysteresis)

        # ---- SIGKILL the oldest replica under live traffic
        victim = next(r for r in st["replicas"] if r["name"] == "r1")
        os.kill(victim["pid"], signal.SIGKILL)
        deadline = time.time() + 60
        while time.time() < deadline:
            st = fleet.status()
            names = {r["name"] for r in st["replicas"]}
            if st["live"] == 2 and "r1" not in names \
                    and all(r["spawn_s"] for r in st["replicas"]):
                break
            time.sleep(0.1)
        st = fleet.status()
        assert st["live"] == 2 and st["degraded"] is None, st
        _bank_compile_baselines(fleet, baselines)
        time.sleep(0.5)                            # traffic on new fleet

        # ---- slack: sustained cold drains back to min (hysteresis
        # already held the fleet at 2 while signals were empty-hot-less)
        sigs["rows"] = [{"name": "x", "firing": [], "queue_depth": 0.0}]
        deadline = time.time() + 60
        while time.time() < deadline and (
                fleet.live_count() > 1
                or len(fleet.status()["replicas"]) > 1):
            time.sleep(0.1)
        st = fleet.status()
        assert fleet.live_count() == 1 and fleet.target == 1
        assert len(st["replicas"]) == 1, st    # drained one is GONE
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=10)

        # ---- only in-flight requests may be lost: a SIGKILL can fail
        # the requests the dead replica was holding (bounded by the
        # router's view of its outstanding count, itself bounded by
        # the 2 client threads), never the rest of the stream
        failures = [s for s, _ in results if s not in (200, 503)]
        assert len(results) > 50
        assert len(failures) <= 2, failures
        ok_lat = sorted(lat for s, lat in results if s == 200)
        assert ok_lat, results
        p99 = ok_lat[min(len(ok_lat) - 1, int(0.99 * len(ok_lat)))]
        assert p99 < 5.0, p99                      # tiny model, huge slack

        # ---- zero XLA compiles after warmup on EVERY replica that is
        # still up, including the warmset-spawned mid-ramp one
        _assert_no_replica_compiled(fleet, baselines)

        # ---- the flight ring tells the story post-mortem
        events, _torn = bb.read_events()
        kinds = [e["event"] for e in events]
        assert "scale_up" in kinds and "scale_down" in kinds \
            and "replica_death" in kinds
        death = next(e for e in events if e["event"] == "replica_death")
        assert death["replica"] == "r1" and death["reason"] == "preempt" \
            and death["respawn"]
        # the respawn scale_up comes AFTER the death record
        i_death = kinds.index("replica_death")
        assert "scale_up" in kinds[i_death:]
        retired = next(e for e in events if e["event"] == "scale_down")
        assert retired["reason"] == "slack"
        front.close()
    finally:
        stop.set()
        fleet.close()
        bb.reset()
