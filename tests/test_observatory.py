"""Cluster observatory + goodput ledger (ISSUE 20).

Fast units cover the ledger's accounting invariants (categories sum to
measured wall, restart-gap crediting, overrun honesty, open-step
overlap), the supervisor's ``MXNET_GOODPUT_PREV_EXIT_TS`` stamp, the
snapshot/diagnostics/SLO surfaces, peer discovery (heartbeat-published
endpoints, fleet roster, dead-peer degradation), the read-only scrape
fence, and the flight-ring merge — including a real subprocess ring
SIGKILLed mid-frame.

The ``slow``-marked chaos acceptance replays the PR 19 SIGKILL run
with per-rank flight rings and the goodput ledger on: the merged
incident timeline must read fault → member_lost → rescale(shrink) →
rescale(grow) in causal order, and the survivor's goodput report must
sum to 100% of wall with the outage attributed to rescale (the
relaunched joiner books its dead time as restart).
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from mxnet_tpu import blackbox
from mxnet_tpu import goodput as gp
from mxnet_tpu import health
from mxnet_tpu import observatory as ob
from mxnet_tpu import telemetry as tm
from mxnet_tpu import tracing as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_ledger():
    gp.reset()
    gp.enable(True)
    ob.configure()                       # clear any installed observatory
    yield
    gp.reset()
    ob.configure()


def _cat_sum(rep):
    return sum(v["seconds"] for v in rep["categories"].values())


# ---------------------------------------------------------------------------
# goodput ledger: the accounting invariants
# ---------------------------------------------------------------------------

def test_ledger_sums_to_wall():
    gp.session_begin()
    tok = gp.step_begin()
    time.sleep(0.03)
    gp.step_end(tok, data_wait_s=0.01)
    time.sleep(0.01)                     # real wall backing the note
    gp.note("checkpoint", 0.005)
    gp.session_end()
    rep = gp.report()
    assert rep["active"] and rep["steps"] == 1
    assert set(rep["categories"]) == set(gp.CATEGORIES)
    # THE invariant: categories (idle residual included) sum to wall
    assert abs(_cat_sum(rep) - rep["wall_s"]) < 1e-4
    assert rep["categories"]["data_wait"]["seconds"] >= 0.01
    assert rep["categories"]["checkpoint"]["seconds"] >= 0.005
    assert rep["categories"]["step_compute"]["seconds"] > 0
    assert rep["overrun_s"] == 0
    assert abs(rep["goodput_fraction"] + rep["badput_fraction"] - 1.0) < 1e-5


def test_ledger_inactive_and_disabled():
    assert gp.report() == {"active": False}
    gp.enable(False)
    gp.session_begin()
    assert not gp.active()
    assert gp.step_begin() is None


def test_note_rejects_idle_and_unknown():
    gp.session_begin()
    with pytest.raises(ValueError):
        gp.note("idle", 1.0)
    with pytest.raises(ValueError):
        gp.note("lunch", 1.0)
    with pytest.raises(ValueError):
        gp.note_since_last("idle")


def test_note_inside_open_step_not_double_counted():
    """A barrier wait booked from INSIDE an open step window must be
    subtracted from that step's compute — the sum stays <= wall."""
    gp.session_begin()
    tok = gp.step_begin()
    time.sleep(0.02)
    gp.note("straggler_wait", 0.015)     # booked mid-step (kv.barrier)
    gp.step_end(tok)
    rep = gp.report()
    assert abs(_cat_sum(rep) - rep["wall_s"]) < 1e-4
    assert rep["overrun_s"] == 0
    assert rep["categories"]["straggler_wait"]["seconds"] >= 0.015
    # step window was ~0.02s of which 0.015 was the wait
    assert rep["categories"]["step_compute"]["seconds"] < 0.02


def test_note_since_last_books_the_gap():
    """The elastic-outage idiom: an interrupted step never reaches
    step_end; note_since_last sweeps everything since the last
    accounting point into the category."""
    gp.session_begin()
    gp.step_begin()                      # the step that will "fail"
    time.sleep(0.02)
    dt = gp.note_since_last("rescale")
    assert dt >= 0.02
    rep = gp.report()
    assert rep["categories"]["rescale"]["seconds"] >= 0.02
    assert abs(_cat_sum(rep) - rep["wall_s"]) < 1e-4


def test_overrun_reported_honestly():
    """Booked time exceeding measured wall (clock skew) scales every
    category down so the report still sums exactly — and reports the
    overage instead of hiding it."""
    gp.session_begin()
    gp.note("checkpoint", 100.0)         # grossly exceeds session wall
    rep = gp.report()
    assert rep["overrun_s"] > 90
    assert abs(_cat_sum(rep) - rep["wall_s"]) < 1e-4
    assert rep["categories"]["idle"]["seconds"] == 0


def test_restart_gap_credited_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_GOODPUT_PREV_EXIT_TS",
                       repr(time.time() - 2.5))
    gp.reset()
    gp.session_begin()
    rep = gp.report()
    restart = rep["categories"]["restart"]["seconds"]
    assert 2.0 < restart < 10.0
    # the gap extends measured wall, so the invariant covers the outage
    assert rep["wall_s"] >= restart
    assert abs(_cat_sum(rep) - rep["wall_s"]) < 1e-4


def test_supervisor_stamps_prev_exit_ts(tmp_path):
    """A relaunched child finds its predecessor's death timestamp in
    the env ProcessSupervisor built for it."""
    from mxnet_tpu.checkpoint import ProcessSupervisor
    marker = str(tmp_path / "seen.json")
    script = str(tmp_path / "child.py")
    with open(script, "w") as f:
        f.write(
            "import json, os, sys\n"
            "ts = os.environ.get('MXNET_GOODPUT_PREV_EXIT_TS')\n"
            "if ts is None: sys.exit(17)\n"           # first launch dies
            "json.dump({'ts': float(ts)}, open(%r, 'w'))\n" % marker)
    sup = ProcessSupervisor(max_failures=3, relaunch_delay_s=0)
    t0 = time.time()
    rc = sup.run([sys.executable, script])
    assert rc == 0 and sup.launches == 2
    seen = json.load(open(marker))
    assert t0 <= seen["ts"] <= time.time()


def test_snapshot_and_diagnostics_bank_goodput():
    gp.session_begin()
    tok = gp.step_begin()
    gp.step_end(tok)
    snap = tm.snapshot()
    assert "goodput_fraction" in snap and "goodput_wall_s" in snap
    for c in gp.CATEGORIES:
        assert "goodput_%s_s" % c in snap
    info = tm.diagnostics(as_dict=True)
    assert info["goodput"]["active"] is True


def test_badput_slo_rule_registered():
    assert "badput_fraction" in health.rules()


def test_ledger_adds_no_device_dispatch():
    """The ledger is host arithmetic: a fused-step loop under the fit
    loop's hooks makes the same number of dispatches (and compiles
    nothing) whether the ledger is on or gated off."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.context import current_context
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.models import mlp
    from mxnet_tpu.module import Module
    mod = Module(mlp(), context=current_context())
    mod.bind(data_shapes=[("data", (8, 784))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    db = DataBatch(
        data=[mx.nd.array(rng.randn(8, 784).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 10, (8,)).astype(np.float32))])

    def loop(on, steps=5):
        gp.enable(on)
        if on and not gp.active():
            gp.session_begin()
        snap0 = tm.snapshot()
        for _ in range(steps):
            tok = gp.step_begin()
            mod.forward_backward(db)
            mod.update()
            gp.step_end(tok)
        snap1 = tm.snapshot()
        return (snap1["op_dispatch_total"] - snap0["op_dispatch_total"],
                snap1["backend_compile_total"]
                - snap0["backend_compile_total"])

    loop(False), loop(True)              # warm both gate states
    off, on = loop(False), loop(True)
    assert off[0] > 0 and on == off and on[1] == 0


def test_goodput_gauges_exported():
    gp.session_begin()
    for i in range(8):                   # gauge refresh is every 8th step
        gp.step_end(gp.step_begin())
    text = tm.render_prometheus()
    assert "mxnet_goodput_wall_seconds" in text
    assert 'mxnet_goodput_category_seconds{category="step_compute"}' in text
    assert "mxnet_goodput_badput_fraction" in text


# ---------------------------------------------------------------------------
# observatory: discovery, degradation, fence, /cluster
# ---------------------------------------------------------------------------

def test_cluster_endpoint_unconfigured(monkeypatch):
    monkeypatch.delenv("MXNET_ELASTIC_DIR", raising=False)
    code, payload = ob.cluster_endpoint("")
    assert code == 200 and payload == {"configured": False}


def test_cluster_mounted_on_telemetry_serve(monkeypatch):
    monkeypatch.delenv("MXNET_ELASTIC_DIR", raising=False)
    with tm.serve(port=0) as srv:
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/cluster" % srv.port, timeout=5).read()
    assert json.loads(body) == {"configured": False}


def test_cluster_mounted_on_serve_http(monkeypatch):
    monkeypatch.delenv("MXNET_ELASTIC_DIR", raising=False)
    from mxnet_tpu.serve.http import serve_http
    srv = serve_http(object(), port=0)   # GET /cluster needs no engine
    try:
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/cluster" % srv.port, timeout=5).read()
        assert json.loads(body) == {"configured": False}
        # the serving mount publishes itself as the scrapable endpoint
        assert tm.server_endpoint() == "127.0.0.1:%d" % srv.port
    finally:
        srv.close()


def test_dead_peer_degrades_to_counter():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()                            # nobody listens there now
    o = ob.Observatory(peers=(dead,), timeout_s=0.3)
    view = o.cluster_view()              # must not raise
    assert view["peer_count"] == 1
    assert view["peers"][0]["ok"] is False
    assert view["scrape_failures_total"] >= 3   # alerts+metrics+traces
    fam = tm.REGISTRY._families.get("observatory/scrape_failures_total")
    assert fam is not None and sum(c.value for _lv, c in fam.series()) >= 3


def test_heartbeat_publishes_endpoint_and_discovery(tmp_path):
    """An elastic rank's heartbeat carries its telemetry endpoint; the
    observatory discovers the rank from the heartbeat file alone and
    scrapes it."""
    from mxnet_tpu.elastic import ElasticAgent
    with tm.serve(port=0) as srv:
        agent = ElasticAgent(root=str(tmp_path), rank=0, world=1,
                             base_world=1, hb_s=999, dead_s=999)
        agent._beat()
        rec = json.load(open(tmp_path / "hb-g1-r0.json"))
        assert rec["telemetry"] == "127.0.0.1:%d" % srv.port
        o = ob.Observatory(elastic_dir=str(tmp_path))
        peers = o.discover()
        assert [p["name"] for p in peers] == ["rank0"]
        view = o.cluster_view()
        assert view["peers"][0]["ok"] is True
        assert view["scrape_failures_total"] == 0


def test_fleet_roster_peers_discovered():
    status = {"replicas": [{"name": "r0", "pid": 1, "port": 18341,
                            "endpoint": "127.0.0.1:18341",
                            "retiring": False, "warm": True,
                            "spawn_s": 0.1},
                           {"name": "r1", "pid": 2, "port": None,
                            "endpoint": None, "retiring": False,
                            "warm": False, "spawn_s": 0.1}]}

    class _FakeFleet(object):
        def status(self):
            return status
    o = ob.Observatory(fleet=_FakeFleet())
    peers = o.discover()
    # portless (still-spawning) replicas are skipped, not scraped
    assert peers == [{"name": "r0", "kind": "replica",
                      "host": "127.0.0.1", "port": 18341}]


def test_scrape_is_fenced_and_read_only():
    """The bugfix contract: observatory HTTP activity runs under the
    compile-tracking fence, so a scrape — even of this very process —
    cannot perturb compile counts or dispatch totals."""
    fenced = []
    real_get = ob._http_get

    def spying_get(host, port, path, timeout=2.0):
        fenced.append(getattr(tm._suppress, "on", 0) > 0)
        # a compile event arriving mid-scrape (any jax activity on
        # this thread) must NOT be counted — same fence as cost
        # analysis
        tm._on_jax_event("/jax/backend_compile_duration", 123.0)
        return real_get(host, port, path, timeout)

    with tm.serve(port=0) as srv:
        o = ob.Observatory(peers=("127.0.0.1:%d" % srv.port,))
        compiles0 = tm.compile_count()
        ctime0 = tm.compile_time()
        snap0 = tm.snapshot()["op_dispatch_total"]
        ob._http_get, _saved = spying_get, ob._http_get
        try:
            view = o.cluster_view()
        finally:
            ob._http_get = _saved
    assert view["peers"][0]["ok"] is True
    assert fenced and all(fenced), "scrape ran outside the fence"
    assert tm.compile_count() == compiles0
    assert tm.compile_time() == ctime0
    assert tm.snapshot()["op_dispatch_total"] == snap0


def test_self_scrape_merges_own_goodput():
    gp.session_begin()
    for _ in range(8):
        gp.step_end(gp.step_begin())
    gp.session_end()
    with tm.serve(port=0) as srv:
        o = ob.Observatory(peers=("127.0.0.1:%d" % srv.port,))
        view = o.cluster_view()
        summary = o.summary()
    gp_row = view["goodput"]["peer0"]
    assert set(gp_row["categories"]) == set(gp.CATEGORIES)
    assert "goodput_fraction" in gp_row
    assert summary["peers"] == 1 and summary["peers_ok"] == 1
    assert "goodput" in summary


def test_diagnostics_embeds_cluster_summary(monkeypatch):
    with tm.serve(port=0) as srv:
        ob.configure(peers=("127.0.0.1:%d" % srv.port,))
        info = tm.diagnostics(as_dict=True)
    assert info["cluster"]["peers"] == 1
    assert info["cluster"]["peers_ok"] == 1
    assert isinstance(info["cluster"]["alerts_firing"], list)


# ---------------------------------------------------------------------------
# flight-ring merge
# ---------------------------------------------------------------------------

def test_merge_rings_in_process(tmp_path):
    a, b = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
    blackbox.configure(a)
    blackbox.record_event("checkpoint", file="ck0", seconds=0.1)
    blackbox.record_event("alert", rule="r", state="firing", value=1.0)
    blackbox.configure(b)
    blackbox.record_event("checkpoint", file="ck1", seconds=0.2)
    blackbox.configure(None)
    merged = blackbox.merge_rings([a, b])
    names = [(e["event"], e["ring"]) for e in merged["events"]
             if e["event"] != "start"]
    assert names == [("checkpoint", a), ("alert", a), ("checkpoint", b)]
    ts = [e["t"] for e in merged["events"]]
    assert ts == sorted(ts)
    assert merged["abandoned"] == {a: 0, b: 0}
    # per-ring reads and the merge agree exactly: no loss, no dup
    for ring in (a, b):
        own, _torn = blackbox.read_events(ring)
        assert [e["event"] for e in merged["events"]
                if e["ring"] == ring] == [e["event"] for e in own]


def test_merge_rings_missing_ring_degrades(tmp_path):
    a = str(tmp_path / "a.bin")
    blackbox.configure(a)
    blackbox.record_event("checkpoint", file="ck", seconds=0.1)
    blackbox.configure(None)
    gone = str(tmp_path / "nope.bin")
    merged = blackbox.merge_rings([a, gone])
    assert any(e["event"] == "checkpoint" for e in merged["events"])
    assert merged["abandoned"][gone] == 0


_RING_WORKER = r'''
import json, os, signal, struct, sys, time, zlib
path, torn = sys.argv[1], int(sys.argv[2])
from mxnet_tpu import blackbox as bb
bb.configure(path)
for i in range(3):
    bb.record_event("checkpoint", file="ck%d" % i, seconds=0.01)
if torn:
    # the killer names itself before dying (fsync'd fault record)...
    bb.record_event("fault", point="test.kill", kind="crash", hit=1)
    # ...then the process is SIGKILLed mid-frame: a valid header whose
    # payload never finished hitting the disk
    payload = json.dumps({"t": time.time(), "pid": os.getpid(),
                          "event": "checkpoint"}).encode()
    frame = struct.pack("<4sII", b"FR\x00\x00", len(payload),
                        zlib.crc32(payload) & 0xFFFFFFFF) + payload[:9]
    with open(path, "ab") as f:
        f.write(frame)
        f.flush()
        os.fsync(f.fileno())
    print("TORN %d" % (struct.calcsize("<4sII") + 9), flush=True)
    os.kill(os.getpid(), signal.SIGKILL)
print("DONE", flush=True)
'''


def test_merge_rings_subprocess_sigkill_torn_tail(tmp_path):
    """Two real subprocess rings — one SIGKILLed mid-frame — merge
    into one ordered timeline: the killer fault event is present, the
    torn ring reports its abandoned bytes, and nothing is lost or
    duplicated."""
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_RING_WORKER)
    ra, rb = str(tmp_path / "flight-a.bin"), str(tmp_path / "flight-b.bin")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    pa = subprocess.run([sys.executable, script, ra, "1"], env=env,
                        capture_output=True, text=True, timeout=120)
    assert pa.returncode == -signal.SIGKILL, pa.stdout + pa.stderr
    torn_bytes = int(pa.stdout.split("TORN ")[1].split()[0])
    pb = subprocess.run([sys.executable, script, rb, "0"], env=env,
                        capture_output=True, text=True, timeout=120)
    assert pb.returncode == 0, pb.stdout + pb.stderr

    merged = blackbox.merge_rings([ra, rb])
    # torn tail accounted per ring, clean ring untouched
    assert merged["abandoned"] == {ra: torn_bytes, rb: 0}
    # the killer is in the timeline, from the SIGKILLed ring
    faults = [e for e in merged["events"] if e["event"] == "fault"]
    assert len(faults) == 1 and faults[0]["ring"] == ra
    assert faults[0]["kind"] == "crash"
    # ordered by time; ring A ran (and died) strictly before ring B
    ts = [e["t"] for e in merged["events"]]
    assert ts == sorted(ts)
    last_a = max(i for i, e in enumerate(merged["events"])
                 if e["ring"] == ra)
    first_b = min(i for i, e in enumerate(merged["events"])
                  if e["ring"] == rb)
    assert last_a < first_b
    # no loss, no duplication vs each ring read on its own
    for ring in (ra, rb):
        own, _ = blackbox.read_events(ring)
        assert [e["event"] for e in merged["events"]
                if e["ring"] == ring] == [e["event"] for e in own]

    # the CLI produces the same merged timeline
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.observatory",
         "--merge", ra, rb, "--json"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    cli = json.loads(out.stdout)
    assert [e["event"] for e in cli["events"]] == \
        [e["event"] for e in merged["events"]]
    assert cli["abandoned"] == {ra: torn_bytes, rb: 0}


# ---------------------------------------------------------------------------
# cross-process skew + stitching (two live peers)
# ---------------------------------------------------------------------------

_PEER_WORKER = r'''
import json, os, sys, time
rank, eldir, dur = int(sys.argv[1]), sys.argv[2], float(sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
from mxnet_tpu import telemetry as tm
from mxnet_tpu import tracing as tr
tr.set_sample(1.0)
srv = tm.serve(port=0)
for i in range(4):
    with tr.start_span("train.step", attrs={"epoch": 0, "nbatch": i}):
        time.sleep(dur)
rec = {"ts": time.time(), "rank": rank, "pid": os.getpid(),
       "host": "127.0.0.1", "telemetry": "127.0.0.1:%d" % srv.port}
tmp = os.path.join(eldir, ".tmp-%d" % rank)
with open(tmp, "w") as f:
    json.dump(rec, f)
os.rename(tmp, os.path.join(eldir, "hb-g1-r%d.json" % rank))
print("READY", flush=True)
time.sleep(300)
'''


def test_skew_and_stitching_across_two_peers(tmp_path):
    """Two live peers with a 5x injected straggler delay: the
    observatory names the straggler, sets the per-rank and skew
    gauges, and stitches per-global-step cluster.step entries from
    both ranks' train.step summaries."""
    script = str(tmp_path / "peer.py")
    with open(script, "w") as f:
        f.write(_PEER_WORKER)
    eldir = str(tmp_path / "el")
    os.makedirs(eldir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = []
    try:
        for rank, dur in ((0, 0.01), (1, 0.05)):
            procs.append(subprocess.Popen(
                [sys.executable, script, str(rank), eldir, str(dur)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        for p in procs:
            line = p.stdout.readline()
            assert "READY" in line, line

        prev_sample = tr.set_sample(1.0)
        try:
            o = ob.Observatory(elastic_dir=eldir)
            view = o.cluster_view()
        finally:
            tr.set_sample(prev_sample)

        assert view["peer_count"] == 2
        assert view["scrape_failures_total"] == 0
        # straggler named, skew ≈ 40ms
        assert view["skew"]["straggler"] == "rank1"
        assert view["skew"]["skew_s"] > 0.02
        # per-rank gauges + skew gauge materialized
        fam = tm.REGISTRY._families.get("observatory/rank_step_seconds")
        ranks = {lv[0] for lv, _c in fam.series()}
        assert {"rank0", "rank1"} <= ranks
        fam = tm.REGISTRY._families.get("observatory/step_skew_seconds")
        assert sum(c.value for _lv, c in fam.series()) > 0.02
        # stitched global steps: both ranks joined by (epoch, nbatch)
        steps = [s for s in view["steps"] if s["world"] == 2]
        assert len(steps) == 4
        for s in steps:
            assert s["straggler"] == "rank1"
            assert s["skew_ms"] > 20
            assert set(s["ranks"]) == {"rank0", "rank1"}
        # each newly stitched step became a cluster.step marker span
        roots = [t["root"] for t in tr.finished_traces(50)]
        assert roots.count("cluster.step") >= 4
    finally:
        for p in procs:
            p.kill()


# ---------------------------------------------------------------------------
# chaos acceptance: merged incident timeline + goodput over a real kill
# ---------------------------------------------------------------------------

_CHAOS_WORKER = r'''
"""One rank of a 2-process elastic fit with the goodput ledger and a
per-rank flight ring: prints its goodput report when training ends."""
import json, os, sys, time
import numpy as np
rank = int(sys.argv[1])
epochs, nb, L, dim = (int(a) for a in sys.argv[2:6])
pace_s = float(os.environ.get("ELASTIC_TEST_PACE_S", "0"))
joiner = bool(int(os.environ.get("MXNET_ELASTIC_JOIN", "0")))
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
if not joiner:
    os.environ["MXNET_DIST_COORDINATOR"] = os.environ["COORD"]
    os.environ["MXNET_DIST_NUM_PROCESSES"] = "2"
    os.environ["MXNET_DIST_PROCESS_ID"] = str(rank)
import mxnet_tpu as mx
from mxnet_tpu import dist_runtime
from mxnet_tpu import goodput as gp
from mxnet_tpu.module import Module
if not joiner:
    dist_runtime.acquire()

net = mx.sym.Variable("data")
net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
net = mx.sym.Activation(net, name="relu1", act_type="relu")
net = mx.sym.FullyConnected(net, name="fcout", num_hidden=10)
net = mx.sym.SoftmaxOutput(net, name="softmax")

arg_params = None
if not joiner:
    shapes, _, _ = net.infer_shape(data=(L, dim))
    prng = np.random.RandomState(7)
    arg_params = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name not in ("data", "softmax_label"):
            arg_params[name] = mx.nd.array(
            prng.uniform(-0.1, 0.1, shape).astype(np.float32))

N = 2 * nb * L
rng = np.random.RandomState(3)
X = rng.randn(N, dim).astype(np.float32)
Y = rng.randint(0, 10, N).astype(np.float32)
it = mx.io.NDArrayIter(X, Y, batch_size=L, shuffle=True, seed=11,
                       last_batch_handle="discard", num_parts=2,
                       part_index=rank)

def _cb(param):
    if pace_s:
        time.sleep(pace_s)

mod = Module(net, context=mx.cpu())
mod.fit(it, num_epoch=epochs, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        arg_params=arg_params, kvstore="dist_tpu_sync",
        batch_end_callback=_cb)

print("GOODPUT_REPORT " + json.dumps(gp.report()), flush=True)
mod._kvstore.close()
dist_runtime.release()
'''

_EPOCHS, _NB, _L, _DIM = 4, 15, 4, 16


def _chaos_env(eldir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               MXNET_FUSED_STEP="1", MXNET_ELASTIC_DIR=eldir,
               MXNET_ELASTIC_HB_S="0.2", MXNET_DIST_DEAD_S="2.0",
               MXNET_STEP_TIMEOUT_S="60", ELASTIC_TEST_PACE_S="0.25")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"      # see test_elastic.py
    for v in ("MXNET_TPU_PS_URI", "MXNET_FAULT_INJECT",
              "MXNET_ELASTIC_JOIN", "MXNET_FLIGHT_RECORDER",
              "MXNET_GOODPUT_PREV_EXIT_TS"):
        env.pop(v, None)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    env["COORD"] = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()
    return env


def _spawn(script, rank, env, extra):
    argv = [sys.executable, script, str(rank), str(_EPOCHS), str(_NB),
            str(_L), str(_DIM)]
    return subprocess.Popen(argv, env=dict(env, **extra), cwd=ROOT,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _goodput_report(out, who):
    for line in reversed(out.splitlines()):
        if line.startswith("GOODPUT_REPORT "):
            return json.loads(line[len("GOODPUT_REPORT "):])
    raise AssertionError("%s produced no GOODPUT_REPORT: %s"
                         % (who, out[-1500:]))


@pytest.mark.slow
def test_chaos_incident_timeline_and_goodput(tmp_path):
    """The ISSUE 20 acceptance: the PR 19 SIGKILL chaos run, observed.
    Rank 1 dies at the top of its 4th step; afterward the two rings
    merge into ONE incident timeline reading fault → member_lost →
    rescale(shrink) → rescale(grow: the rejoin) in causal order, the
    survivor's goodput ledger sums to 100% of wall with the outage
    attributed to rescale, and the relaunched joiner books its dead
    time as restart via MXNET_GOODPUT_PREV_EXIT_TS."""
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_CHAOS_WORKER)
    eldir = str(tmp_path / "el")
    os.makedirs(eldir)
    ring0 = str(tmp_path / "flight-r0.bin")
    ring1 = str(tmp_path / "flight-r1.bin")
    env = _chaos_env(eldir)

    survivor = _spawn(script, 0, env, {"MXNET_FLIGHT_RECORDER": ring0})
    victim = _spawn(script, 1, env,
                    {"MXNET_FLIGHT_RECORDER": ring1,
                     "MXNET_FAULT_INJECT": "dist.member:4:crash"})
    procs = [survivor, victim]
    try:
        outv = victim.communicate(timeout=600)[0]
        death_ts = time.time()
        assert victim.returncode in (137, -9), (
            "victim should die SIGKILL-grade, got rc=%r: %s"
            % (victim.returncode, outv[-1500:]))
        deadline = time.time() + 120
        while (not [n for n in os.listdir(eldir)
                    if n.startswith("plan-g")]
               and time.time() < deadline):
            time.sleep(0.1)
        # relaunch as a joiner, carrying the supervisor's death stamp
        rejoin = _spawn(script, 1, env,
                        {"MXNET_ELASTIC_JOIN": "1",
                         "MXNET_FLIGHT_RECORDER": ring1,
                         "MXNET_GOODPUT_PREV_EXIT_TS": repr(death_ts)})
        procs.append(rejoin)
        outj = rejoin.communicate(timeout=600)[0]
        assert rejoin.returncode == 0, (
            "joiner failed rc=%r: %s" % (rejoin.returncode, outj[-1500:]))
        outs = survivor.communicate(timeout=600)[0]
        assert survivor.returncode == 0, (
            "survivor failed rc=%r: %s"
            % (survivor.returncode, outs[-1500:]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # -- (a) ONE merged incident timeline, causally ordered -----------
    merged = blackbox.merge_rings([ring0, ring1])
    assert sorted(merged["abandoned"]) == sorted([ring0, ring1])
    seq = [(e["event"], e.get("grow"), e["ring"]) for e in merged["events"]]
    i_fault = next(i for i, e in enumerate(merged["events"])
                   if e["event"] == "fault")
    i_lost = next(i for i, e in enumerate(merged["events"])
                  if e["event"] == "member_lost")
    rescales = [i for i, e in enumerate(merged["events"])
                if e["event"] == "rescale"]
    assert len(rescales) == 2, seq
    i_shrink, i_grow = rescales
    # the killer (victim's ring) precedes the survivor's detection,
    # which precedes the shrink plan, which precedes the rejoin grow
    assert merged["events"][i_fault]["ring"] == ring1
    assert merged["events"][i_fault]["kind"] == "crash"
    assert i_fault < i_lost < i_shrink < i_grow, seq
    shrink, grow = merged["events"][i_shrink], merged["events"][i_grow]
    assert (shrink["old_world"], shrink["world"]) == (2, 1)
    assert shrink["grow"] is False
    assert (grow["old_world"], grow["world"]) == (1, 2)
    assert grow["grow"] is True
    ts = [e["t"] for e in merged["events"]]
    assert ts == sorted(ts)

    # -- (b) goodput: sums to wall, outage attributed -----------------
    surv = _goodput_report(outs, "survivor")
    assert surv["active"] is True
    cats = {c: v["seconds"] for c, v in surv["categories"].items()}
    assert abs(sum(cats.values()) - surv["wall_s"]) \
        < max(1e-3, 1e-5 * surv["wall_s"])
    fr = {c: v["fraction"] for c, v in surv["categories"].items()}
    assert abs(sum(fr.values()) - 1.0) < 1e-3      # 100% of wall
    # the outage (detection + barrier + reinit + both rescales) landed
    # in rescale, and it is substantial vs this short run
    assert cats["rescale"] > 0.5, cats
    assert cats["step_compute"] > 0, cats
    assert surv["overrun_s"] == 0

    join = _goodput_report(outj, "joiner")
    jcats = {c: v["seconds"] for c, v in join["categories"].items()}
    # the relaunch gap (death → joiner session) was booked as restart
    assert jcats["restart"] > 0.5, jcats
    assert abs(sum(jcats.values()) - join["wall_s"]) \
        < max(1e-3, 1e-5 * join["wall_s"])
