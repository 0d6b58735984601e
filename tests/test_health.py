"""Production health layer: MFU/roofline accounting, in-program
numerics sentinels, SLO burn-rate alerts, crash-safe flight recorder.

Acceptance proofs (ISSUE 12):
* a Module.fit run with MXNET_NUMERICS=step shows ZERO extra host
  dispatches per step and ZERO XLA recompiles across LR-schedule steps
  (telemetry-asserted);
* an injected NaN gradient trips the policy within one step and names
  the offending param in ``full`` mode;
* the numerics trip leaves a flight-recorder record still readable
  after the training process is SIGKILLed (rc 137, fault-harness
  subprocess);
* /alerts reports a firing serve-p99 rule under an injected
  slow-compute fault and clears after recovery;
* executor/mfu is present on /metrics after one warmed fused step.
"""
import json
import os
import struct
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import blackbox, fault, health
from mxnet_tpu import telemetry as tm
from mxnet_tpu import tracing as trc
from mxnet_tpu.context import current_context
from mxnet_tpu.io import DataBatch, NDArrayIter
from mxnet_tpu.models import mlp
from mxnet_tpu.module import Module

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _health_isolation():
    prev_mode = health.numerics_mode()
    prev_policy = health.numerics_policy()
    yield
    health.set_numerics(prev_mode)
    health.set_numerics_policy(prev_policy)
    health.reset()
    blackbox.reset()
    fault.disarm()


def _mlp_module(batch=16, seed=0):
    mod = Module(mlp(), context=current_context())
    mod.bind(data_shapes=[("data", (batch, 784))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rng = np.random.RandomState(seed)
    db = DataBatch(
        data=[mx.nd.array(rng.randn(batch, 784).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 10, (batch,))
                           .astype(np.float32))])
    return mod, db


def _serve_mlp_predictor(feature, hidden, classes):
    """A bound Predictor over softmax(FC(relu(FC(data)))) — small, so a
    serving test probes the batching engine and not the matmuls."""
    from mxnet_tpu.serving import Predictor
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1"),
        act_type="relu")
    sym = mx.sym.softmax(
        mx.sym.FullyConnected(h, num_hidden=classes, name="fc2"),
        name="prob")
    rng = np.random.RandomState(0)
    params = {
        "arg:fc1_weight": mx.nd.array(
            rng.randn(hidden, feature).astype(np.float32) * 0.05),
        "arg:fc1_bias": mx.nd.array(np.zeros(hidden, np.float32)),
        "arg:fc2_weight": mx.nd.array(
            rng.randn(classes, hidden).astype(np.float32) * 0.05),
        "arg:fc2_bias": mx.nd.array(np.zeros(classes, np.float32)),
    }
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "p.params")
        mx.nd.save(path, params)
        with open(path, "rb") as f:
            blob = f.read()
    return Predictor(sym.tojson(), blob, dev_type=1,
                     input_shapes={"data": (1, feature)})


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_roundtrip_and_cli(tmp_path):
    path = str(tmp_path / "flight.bin")
    blackbox.configure(path)
    blackbox.record_event("checkpoint", file="ck-0001.params",
                          seconds=0.012)
    blackbox.record_event("swap", quantized=True)
    events, torn = blackbox.read_events(path)
    assert torn == 0
    names = [e["event"] for e in events]
    assert names == ["start", "checkpoint", "swap"]
    assert events[1]["file"] == "ck-0001.params"
    assert all(e["pid"] == os.getpid() for e in events)
    assert blackbox.records_written() == 3
    # the post-mortem CLI reads the same ring from a fresh process
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.blackbox", path, "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert [l["event"] for l in lines] == names


def test_flight_recorder_unknown_event_raises(tmp_path):
    blackbox.configure(str(tmp_path / "f.bin"))
    with pytest.raises(mx.base.MXNetError, match="unknown flight"):
        blackbox.record_event("zap_not_registered")


def test_flight_recorder_disabled_is_noop(tmp_path):
    blackbox.configure(None)
    assert blackbox.record_event("checkpoint", file="x") is False


def test_flight_recorder_torn_tail_tolerated(tmp_path):
    """A SIGKILL can land mid-frame: every frame before the tear must
    still read, and the reader must report the abandoned bytes."""
    path = str(tmp_path / "flight.bin")
    blackbox.configure(path)
    for i in range(5):
        blackbox.record_event("checkpoint", file="ck-%d" % i)
    blackbox.configure(None)             # close the fd
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - 7)         # tear the last frame
    events, torn = blackbox.read_events(path)
    assert torn > 0
    assert [e["event"] for e in events] == \
        ["start"] + ["checkpoint"] * 4   # last record lost, rest intact


def test_flight_recorder_corrupt_frame_stops_segment(tmp_path):
    """A flipped byte mid-ring fails that frame's CRC; the reader
    keeps everything before it rather than trusting garbage."""
    path = str(tmp_path / "flight.bin")
    blackbox.configure(path)
    for i in range(4):
        blackbox.record_event("checkpoint", file="ck-%d" % i)
    blackbox.configure(None)
    with open(path, "rb") as f:
        blob = f.read()
    # find the 3rd frame boundary and corrupt its payload
    hdr = struct.Struct("<4sII")
    off = 0
    for _ in range(2):
        _m, length, _c = hdr.unpack_from(blob, off)
        off += hdr.size + length
    blob = bytearray(blob)
    blob[off + hdr.size + 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))
    events, torn = blackbox.read_events(path)
    assert [e["event"] for e in events] == ["start", "checkpoint"]
    assert torn > 0


def test_flight_recorder_rotation_bounds_disk(tmp_path):
    path = str(tmp_path / "flight.bin")
    blackbox.configure(path, limit_mb=0.01)   # 5 KB per segment
    for i in range(400):
        blackbox.record_event("checkpoint", file="ck-%06d" % i)
    size = os.path.getsize(path) if os.path.exists(path) else 0
    size1 = os.path.getsize(path + ".1") if os.path.exists(path + ".1") \
        else 0
    assert size + size1 <= 2 * 5000 + 4096    # bounded footprint
    events, torn = blackbox.read_events(path)
    assert torn == 0
    # the NEWEST record always survives rotation
    assert events[-1]["file"] == "ck-000399"


# ---------------------------------------------------------------------------
# pillar 1: MFU / roofline
# ---------------------------------------------------------------------------

@pytest.fixture()
def known_device(monkeypatch):
    """Give the test host a row in the peaks table (made-up rates): the
    gauge mechanics are under test, not a roofline."""
    import jax
    monkeypatch.setitem(health.DEVICE_PEAKS, jax.devices()[0].device_kind,
                        {"flops": 1e12, "int8_ops": 2e12,
                         "hbm_bytes_per_s": 1e11})


def test_unknown_device_kind_has_no_peak_and_no_gauge():
    """A device_kind the peaks table does not hold gets NO peak and NO
    live MFU gauge — never another chip's roof."""
    import jax
    assert jax.devices()[0].device_kind not in health.DEVICE_PEAKS
    assert health.device_peaks() is None and health.peak_flops() is None
    assert health._util({"flops": 1e9, "bytes": 1e6}, 0.01) is None
    assert health.note_executor_step({"flops": 1e9, "bytes": 1e6},
                                     0.01) is None
    assert tm.REGISTRY._families.get("executor/mfu") is None \
        or not tm.REGISTRY._families["executor/mfu"].series()
    assert health.mfu_summary()["peak_flops"] is None
    # the one row there is: read off the chip, rates from the published
    # v5e figures
    v5e = health.DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e["flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)


def test_known_device_kind_prices_with_its_row(known_device):
    assert health.peak_flops() == 1e12
    assert health.peak_hbm_bytes_per_s() == 1e11
    mfu, bw = health._util({"flops": 1e9, "bytes": 1e6}, 0.01)
    assert mfu == pytest.approx(0.1) and bw == pytest.approx(1e-3)


def test_mfu_gauges_after_one_warmed_fused_step(known_device):
    """Acceptance: executor/mfu present on /metrics after one warmed
    fused step (plus the captured program's flops are real)."""
    mod, db = _mlp_module()
    for _ in range(3):                   # build + warm + one interval
        mod.forward_backward(db)
        mod.update()
    rec = mod._exec.fused_cost()
    if rec is None:
        pytest.skip("backend returned no cost analysis (documented "
                    "n/a fallback: gauges absent)")
    assert rec["flops"] > 0 and rec["bytes"] > 0
    prom = tm.render_prometheus()
    assert "mxnet_executor_mfu " in prom
    assert "mxnet_executor_hbm_bw_util " in prom
    summary = health.mfu_summary()
    assert summary["programs"]
    assert summary["executor_mfu"] > 0


def test_capture_cost_unknown_kind_raises():
    with pytest.raises(mx.base.MXNetError, match="unknown cost kind"):
        health.capture_cost("nope", "k", None, ())


def test_serve_bucket_mfu_under_traffic(known_device):
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    pred = _serve_mlp_predictor(32, 32, 8)
    eng = InferenceEngine(pred, ServeConfig(max_batch=4, workers=1,
                                            batch_wait_ms=0))
    eng.start().warmup()
    try:
        eng.predict({"data": np.zeros((1, 32), np.float32)})
        if eng._bucket_cost.get(1) is None:
            pytest.skip("no cost analysis on this backend")
        prom = tm.render_prometheus()
        assert 'mxnet_serving_mfu{bucket="1"}' in prom
    finally:
        eng.close(drain=False)


def test_concurrent_engines_price_batches_with_own_costs():
    """Two live engines (the shadow-A/B / swap-drain shape) must not
    share one global bucket cost record: each prices its batches with
    ITS program's FLOPs."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    engines = []
    try:
        for hidden in (16, 64):          # different-size models
            pred = _serve_mlp_predictor(16, hidden, 4)
            eng = InferenceEngine(pred, ServeConfig(max_batch=2,
                                                    workers=1,
                                                    batch_wait_ms=0))
            eng.start().warmup()
            eng.predict({"data": np.zeros((1, 16), np.float32)})
            engines.append(eng)
        a, b = engines[0]._bucket_cost.get(1), \
            engines[1]._bucket_cost.get(1)
        if a is None or b is None:
            pytest.skip("no cost analysis on this backend")
        # distinct programs, distinct records — the bigger model costs
        # more flops, and neither engine clobbered the other
        assert a["flops"] != b["flops"]
    finally:
        for eng in engines:
            eng.close(drain=False)


def test_single_event_fires_events_mode_rule():
    """A counter-delta rule in events mode fires on ONE event and
    clears once the short window drains — burn-fraction dilution
    across quiet evaluator ticks must not swallow a numerics trip."""
    box = {"v": None}
    rule = health._Rule("unit_ev", lambda: box["v"], threshold=0.0,
                        cmp=">", short_s=2.0, long_s=6.0, burn=0.5,
                        description="", mode="events")
    t = 100.0
    for i in range(5):                   # long quiet steady state
        box["v"] = 0.0
        state, _ = rule.evaluate(t + i)
        assert state == "ok"
    box["v"] = 1.0                       # ONE event
    state, trans = rule.evaluate(t + 5)
    assert state == "firing" and trans
    box["v"] = 0.0
    state, _ = rule.evaluate(t + 6)      # still inside short window
    assert state == "firing"
    state, trans = rule.evaluate(t + 9)  # short window drained
    assert state == "ok" and trans
    # the default delta rules run in events mode
    for name in ("numerics", "kv_giveups", "worker_restart_burn"):
        health.rules()                   # install defaults
        assert health._rules[name].mode == "events"


# ---------------------------------------------------------------------------
# pillar 2: numerics sentinels
# ---------------------------------------------------------------------------

def test_acceptance_step_mode_zero_dispatch_zero_recompile():
    """Acceptance: a Module.fit run with MXNET_NUMERICS=step on the
    fused-step probe shows zero extra host dispatches per step and
    zero XLA recompiles across LR-schedule steps — telemetry-asserted.
    The LR scheduler changes the learning rate EVERY step, so a
    sentinel that baked scalars into the program would recompile."""
    health.set_numerics("step")
    batch, nbatch = 16, 8
    rng = np.random.RandomState(0)
    X = rng.randn(batch * nbatch, 784).astype(np.float32)
    y = rng.randint(0, 10, (batch * nbatch,)).astype(np.float32)

    def make_it():
        return NDArrayIter(X, y, batch_size=batch)

    mod = Module(mlp(), context=current_context())
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.95)
    opt_params = {"learning_rate": 0.05, "momentum": 0.9,
                  "lr_scheduler": sched}

    def fit_epoch():
        mod.fit(make_it(), num_epoch=1, optimizer="sgd",
                optimizer_params=opt_params,
                initializer=mx.init.Uniform(0.1))

    def measured_epoch():
        snap0 = tm.snapshot()
        fit_epoch()
        snap1 = tm.snapshot()
        return {k: snap1[k] - snap0[k]
                for k in ("op_dispatch_total", "backend_compile_total",
                          "fused_step_total", "fused_step_compiles")}

    # baseline: sentinels OFF, warm then measure one epoch
    health.set_numerics("off")
    fit_epoch()
    base = measured_epoch()
    # sentinels ON: the mode is a build-time knob, so one warm epoch
    # re-specializes the program; the epoch after must be identical
    health.set_numerics("step")
    fit_epoch()
    delta = measured_epoch()
    assert delta["fused_step_total"] == nbatch
    # ZERO extra host dispatches per step vs the sentinel-off baseline
    # (the only per-step dispatch is the one fused_train_step; the
    # epoch-boundary param-sync copies are identical in both modes)
    assert delta["op_dispatch_total"] == base["op_dispatch_total"]
    # and ZERO recompiles though the LR changed every step
    assert delta["backend_compile_total"] == 0
    assert delta["fused_step_compiles"] == 0
    # the sentinel actually ran: gauges are live
    assert tm.REGISTRY._families.get("health/grad_norm") is not None


def test_nan_trips_within_one_step():
    health.set_numerics("step")
    health.set_numerics_policy("raise")
    mod, db = _mlp_module()
    for _ in range(2):
        mod.forward_backward(db)
        mod.update()
    mod._exec.flush_numerics()           # healthy so far
    bad = DataBatch(
        data=[mx.nd.array(np.full((16, 784), np.nan, np.float32))],
        label=db.label)
    trips0 = health.numerics_trips()
    mod.forward_backward(bad)
    mod.update()                         # verdict is read one step
    with pytest.raises(health.NumericsError) as ei:
        mod._exec.flush_numerics()       # ...deferred: within one step
    assert "nonfinite" in str(ei.value)
    assert health.numerics_trips() == trips0 + 1
    assert ei.value.report["nonfinite"] > 0


def test_full_mode_names_offending_param():
    health.set_numerics("full")
    health.set_numerics_policy("raise")
    mod, db = _mlp_module()
    mod.forward_backward(db)
    mod.update()
    bad = DataBatch(
        data=[mx.nd.array(np.full((16, 784), np.nan, np.float32))],
        label=db.label)
    mod.forward_backward(bad)
    mod.update()
    with pytest.raises(health.NumericsError) as ei:
        mod._exec.flush_numerics()
    msg = str(ei.value)
    assert "worst param" in msg
    assert any(p in msg for p in mod._param_names)
    per_param = ei.value.report["per_param"]
    assert set(per_param) == set(mod._param_names)
    assert sum(v["nonfinite"] for v in per_param.values()) > 0


def test_warn_policy_continues_training():
    health.set_numerics("step")
    health.set_numerics_policy("warn")
    mod, db = _mlp_module()
    mod.forward_backward(db)
    mod.update()
    bad = DataBatch(
        data=[mx.nd.array(np.full((16, 784), np.nan, np.float32))],
        label=db.label)
    trips0 = health.numerics_trips()
    mod.forward_backward(bad)
    mod.update()
    mod._exec.flush_numerics()           # warn: no raise
    assert health.numerics_trips() == trips0 + 1


def test_checkpoint_and_raise_saves_forensic_checkpoint(tmp_path):
    health.set_numerics("step")
    health.set_numerics_policy("checkpoint-and-raise")
    batch, nbatch = 16, 4
    rng = np.random.RandomState(0)
    X = rng.randn(batch * nbatch, 784).astype(np.float32)
    X[batch:2 * batch] = np.nan          # NaN batch mid-epoch
    y = rng.randint(0, 10, (batch * nbatch,)).astype(np.float32)
    prefix = str(tmp_path / "ck")
    mod = Module(mlp(), context=current_context())
    with pytest.raises(health.NumericsError):
        mod.fit(NDArrayIter(X, y, batch_size=batch), num_epoch=2,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                initializer=mx.init.Uniform(0.1),
                checkpoint_prefix=prefix)
    forensic = [f for f in os.listdir(str(tmp_path))
                if f.startswith("ck.numerics") and
                f.endswith(".params")]
    assert forensic, os.listdir(str(tmp_path))
    # the recovery chain under the PLAIN prefix is untouched by the
    # forensic save (nothing valid yet, and nothing clobbered)
    from mxnet_tpu.checkpoint import load_latest_valid
    assert load_latest_valid(prefix) is None


def test_grad_spike_trips():
    health.set_numerics("step")
    health.set_numerics_policy("raise")
    prev = health.set_spike_factor(3.0)
    try:
        mod, db = _mlp_module()
        for _ in range(4):               # establish the EMA
            mod.forward_backward(db)
            mod.update()
        mod._exec.flush_numerics()
        big = DataBatch(
            data=[mx.nd.array(np.full((16, 784), 1e4, np.float32))],
            label=db.label)
        mod.forward_backward(big)
        mod.update()
        with pytest.raises(health.NumericsError, match="grad_spike"):
            mod._exec.flush_numerics()
    finally:
        health.set_spike_factor(prev)


def test_acceptance_sigkill_leaves_readable_flight_record(tmp_path):
    """Acceptance: train with MXNET_NUMERICS=step and the flight
    recorder on, trip a NaN sentinel (policy warn → recorded, training
    continues), then SIGKILL the process via an armed crash fault two
    steps later (rc 137). The numerics_trip AND the fault's own record
    must both read back from the ring post-mortem."""
    rec_path = str(tmp_path / "flight.bin")
    script = tmp_path / "train.py"
    script.write_text(
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.io import NDArrayIter\n"
        "from mxnet_tpu.models import mlp\n"
        "from mxnet_tpu.module import Module\n"
        "from mxnet_tpu.context import current_context\n"
        "batch = 16\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.randn(batch * 8, 784).astype(np.float32)\n"
        "X[batch:2*batch] = np.nan\n"   # trips at step 2
        "y = rng.randint(0, 10, (batch * 8,)).astype(np.float32)\n"
        "mod = Module(mlp(), context=current_context())\n"
        "mod.fit(NDArrayIter(X, y, batch_size=batch), num_epoch=2,\n"
        "        optimizer='sgd',\n"
        "        optimizer_params={'learning_rate': 0.05},\n"
        "        initializer=mx.init.Uniform(0.1))\n"
        "raise SystemExit(0)\n")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               MXNET_NUMERICS="step",
               MXNET_NUMERICS_POLICY="warn",
               MXNET_FLIGHT_RECORDER=rec_path,
               MXNET_FAULT_INJECT="engine.step:5:crash",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=REPO_ROOT, capture_output=True,
                          timeout=300)
    assert proc.returncode == 137, proc.stderr.decode()[-2000:]
    events, _torn = blackbox.read_events(rec_path)
    names = [e["event"] for e in events]
    assert "numerics_trip" in names      # survived the SIGKILL
    trip = events[names.index("numerics_trip")]
    assert trip["kind"] == "nonfinite"
    # the crash fault wrote its own record before os._exit: the ring
    # names its killer
    assert names[-1] == "fault"
    assert events[-1]["point"] == "engine.step"
    assert events[-1]["kind"] == "crash"
    # and the reader CLI agrees from a fresh process
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.blackbox", rec_path],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0
    assert "numerics_trip" in proc.stdout


# ---------------------------------------------------------------------------
# pillar 3: SLO engine
# ---------------------------------------------------------------------------

def test_default_rules_registered():
    names = health.rules()
    for n in ("serve_p99", "decode_itl_p99", "queue_depth",
              "worker_restart_burn", "kv_giveups", "numerics"):
        assert n in names


def test_watch_validation():
    with pytest.raises(mx.base.MXNetError, match="exactly one"):
        health.watch("bad_rule")
    with pytest.raises(mx.base.MXNetError, match="exactly one"):
        health.watch("bad_rule", gauge="a/b", counter_delta="c/d")


def test_multiwindow_burn_rate_semantics():
    """A one-sample blip cannot fire; a sustained violation fires once
    both windows burn; recovery clears when the short window drops."""
    box = {"v": 0.0}
    rule = health._Rule("unit", lambda: box["v"], threshold=1.0,
                        cmp=">", short_s=2.0, long_s=6.0, burn=0.5,
                        description="")
    t = 100.0
    # one blip inside an otherwise-clean history: no fire
    for i in range(6):
        box["v"] = 5.0 if i == 2 else 0.0
        state, trans = rule.evaluate(t + i)
        assert state == "ok"
    # sustained violation: fires (both windows past burn)
    t += 10
    fired = False
    for i in range(8):
        box["v"] = 5.0
        state, trans = rule.evaluate(t + i)
        fired = fired or state == "firing"
    assert fired
    # recovery: clean short window clears it
    t += 20
    for i in range(6):
        box["v"] = 0.0
        state, _ = rule.evaluate(t + i)
    assert state == "ok"


def test_acceptance_alerts_fire_and_clear_under_slow_compute():
    """Acceptance: /alerts reports a firing serve-p99 rule under an
    injected slow-compute fault and clears after recovery — through a
    real InferenceEngine and the HTTP endpoint."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    pred = _serve_mlp_predictor(32, 32, 8)
    eng = InferenceEngine(pred, ServeConfig(max_batch=4, workers=1,
                                            batch_wait_ms=0,
                                            default_timeout_ms=20000))
    eng.start().warmup()
    health.set_interval(0.05)
    # the default serve_p99 rule with test-speed windows/threshold
    health.watch("serve_p99", histogram_p99="serving/request_seconds",
                 threshold=0.040, short_s=0.5, long_s=1.0, burn=0.5,
                 description="test serve p99")
    srv = tm.serve()
    feed = {"data": np.zeros((1, 32), np.float32)}

    def alerts():
        with urllib.request.urlopen(srv.url + "/alerts",
                                    timeout=5) as r:
            return json.loads(r.read())

    try:
        # slow-compute fault: every worker iteration eats a 70 ms
        # delay, pushing request p99 far past the 40 ms threshold
        fault.arm("serve.worker", step=1, kind="delay", count=10 ** 6,
                  delay_ms=70)
        deadline = time.time() + 20
        firing = []
        while time.time() < deadline:
            eng.predict(feed)
            firing = alerts()["firing"]
            if "serve_p99" in firing:
                break
        assert "serve_p99" in firing, alerts()
        # recovery: disarm, keep traffic flowing so fresh (fast)
        # samples land in the short window
        fault.disarm("serve.worker")
        deadline = time.time() + 20
        while time.time() < deadline:
            eng.predict(feed)
            firing = alerts()["firing"]
            if "serve_p99" not in firing:
                break
            time.sleep(0.02)
        assert "serve_p99" not in firing, alerts()
        body = alerts()
        row = [r for r in body["rules"] if r["name"] == "serve_p99"][0]
        assert row["state"] == "ok"
        assert body["evaluator_alive"]
        # transitions were recorded: counter + flight-style history
        fam = tm.REGISTRY._families.get("health/alert_transitions_total")
        states = {lv for lv, _c in fam.series()}
        assert ("serve_p99", "firing") in states
        assert ("serve_p99", "ok") in states
    finally:
        fault.disarm()
        srv.close()
        eng.close(drain=False)


def test_alerts_endpoint_on_serve_http():
    """The serving frontend mounts the SAME /alerts implementation."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig, serve_http
    pred = _serve_mlp_predictor(16, 16, 4)
    eng = InferenceEngine(pred, ServeConfig(max_batch=2, workers=1))
    eng.start().warmup()
    srv = serve_http(eng)
    try:
        with urllib.request.urlopen(srv.url + "/alerts", timeout=5) as r:
            body = json.loads(r.read())
        assert "rules" in body and "firing" in body
        assert any(r["name"] == "serve_p99" for r in body["rules"])
    finally:
        srv.close()
        eng.close(drain=False)


def test_snapshot_and_diagnostics_carry_health_fields():
    snap = tm.snapshot()
    assert "alerts_firing" in snap
    assert "numerics_trips" in snap
    assert "flight_records" in snap
    info = tm.diagnostics(as_dict=True)
    assert "health" in info
    assert "mfu" in info["health"]
    assert "alerts_firing" in info["health"]


# ---------------------------------------------------------------------------
# satellite: registry/trace-ring vs SLO evaluator concurrency
# ---------------------------------------------------------------------------

def test_concurrent_writers_vs_slo_reader():
    """Telemetry writers + trace-ring writers hammering while the SLO
    evaluator and the scrape path read: no torn snapshots (counter
    totals add up exactly), no deadlock, p99 evaluation keeps
    working."""
    c = tm.counter("serving/requests_total", "x")
    h = tm.histogram("serving/request_seconds", "x")
    health.set_interval(0.02)
    health.watch("conc_unit", histogram_p99="serving/request_seconds",
                 threshold=1e9, short_s=0.5, long_s=1.0, burn=0.5,
                 description="concurrency probe")
    n_threads, per_thread = 8, 400
    stop = threading.Event()
    errs = []

    def writer(i):
        try:
            for k in range(per_thread):
                c.inc()
                h.observe(1e-4 * (k % 7), trace_id="t%d" % i)
                with trc.start_span("train.step",
                                    attrs={"epoch": 0, "nbatch": k}):
                    pass
        except Exception as e:           # pragma: no cover
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                tm.REGISTRY.render_prometheus()
                tm.snapshot()
                health.evaluate_once()
                trc.finished_traces(limit=5)
        except Exception as e:           # pragma: no cover
            errs.append(e)

    c0 = c.value
    rt = threading.Thread(target=reader)
    rt.start()
    ts = [threading.Thread(target=writer, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    stop.set()
    rt.join(timeout=10)
    assert not errs, errs
    assert c.value - c0 == n_threads * per_thread   # no lost bumps
    assert h._default().count >= n_threads * per_thread


def test_exemplar_expiry_still_enforced(monkeypatch):
    """The worst-recent exemplar decays: after the window a stale
    exemplar reads as None instead of pointing at an evicted
    timeline (PR 5 contract, re-asserted under the new reader)."""
    h = tm.Histogram()
    h.observe(0.5, trace_id="abc")
    assert h.exemplar()[1] == "abc"
    monkeypatch.setattr(tm, "EXEMPLAR_WINDOW_S", 0.0)
    time.sleep(0.01)
    assert h.exemplar() is None
    assert h.exemplar() is None          # stays cleared


# ---------------------------------------------------------------------------
# satellite: docs drift
# ---------------------------------------------------------------------------

def test_docs_drift_check_covers_events_and_rules():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import check_metrics_docs as chk
    finally:
        sys.path.pop(0)
    _m, _s, events, rules, endpoints = chk.collect_code_names()
    assert set(blackbox.EVENTS) <= events
    assert {"serve_p99", "numerics", "kv_giveups",
            "badput_fraction"} <= rules
    assert {"/metrics", "/alerts", "/programs"} <= endpoints
    drift = chk.check()
    assert not any(drift.values()), drift
