"""Compiler forensics layer (mxnet_tpu/forensics.py): per-program HLO
capture, fusion-boundary roofline attribution, cross-run diffing.

Acceptance proofs (ISSUE 16):
* a warmed fused train step yields a report whose per-fusion
  flops/bytes sums reconcile with the program's own cost_analysis()
  totals within the documented tolerance;
* enabling capture adds ZERO counted XLA compiles and ZERO extra
  per-step host dispatches (telemetry-asserted);
* a diff across two genuinely different compilations flags a real
  fusion difference and leaves a flight-recorder ``forensics`` event;
* report artifacts survive a roundtrip, and a torn/corrupt file is
  CRC-detected and skipped by the fallback walk, never raised;
* ``GET /programs`` answers on BOTH HTTP mounts (telemetry.serve and
  serve.serve_http), including ``?key=`` and 404;
* a backend without HLO text degrades to the documented n/a stanza
  (counter + report field), never an exception on the capture path.
"""
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import blackbox, forensics as fx, health
from mxnet_tpu import programs as pg
from mxnet_tpu import telemetry as tm
from mxnet_tpu.context import current_context
from mxnet_tpu.io import DataBatch
from mxnet_tpu.models import mlp
from mxnet_tpu.module import Module

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _forensics_isolation():
    yield
    fx.reset()
    health.reset()
    blackbox.reset()


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


def _capture_pair(tmp_path):
    """Two hand-built jitted programs differing by one real op (an
    extra transpose+matmul), captured into tmp_path — a genuine fusion
    difference for the diff tests."""
    import jax
    import jax.numpy as jnp
    fx.configure(on=True, directory=str(tmp_path))

    def f_a(x, w):
        return jnp.tanh(x @ w) * 2.0 + 1.0

    def f_b(x, w):
        return (jnp.tanh(x @ w) * 2.0 + 1.0).T @ jnp.ones((8, 8),
                                                          jnp.float32)

    x = jnp.zeros((8, 128), jnp.float32)
    w = jnp.zeros((128, 8), jnp.float32)
    ra = fx.maybe_capture(pg.ProgramKey("executor_forward", "g-a",
                                        {"v": "a"}), jax.jit(f_a), (x, w))
    rb = fx.maybe_capture(pg.ProgramKey("executor_forward", "g-b",
                                        {"v": "b"}), jax.jit(f_b), (x, w))
    assert not ra.get("unavailable") and not rb.get("unavailable")
    return ra, rb


# ---------------------------------------------------------------------------
# capture + attribution
# ---------------------------------------------------------------------------

def test_fused_step_report_reconciles(tmp_path):
    """E2E: the fused train step's report has a real per-fusion
    inventory whose flops/bytes sums reconcile with cost_analysis()."""
    fx.configure(on=True, directory=str(tmp_path))
    mod, db = _mlp_module()
    for _ in range(3):
        mod.forward_backward(db)
        mod.update()
    reps = [r for r in fx.reports().values() if r["kind"] == "fused_step"]
    assert len(reps) == 1
    rep = reps[0]
    assert rep["fusions"], "optimized HLO parsed to zero fusions"
    # ranked by boundary bytes, shares normalized against module total
    bl = [f["bytes"] for f in rep["fusions"]]
    assert bl == sorted(bl, reverse=True)
    assert all(0.0 <= f["bytes_share"] <= 1.0 for f in rep["fusions"])
    # internal consistency: fusion bytes + residual bytes == totals
    total = sum(bl) + rep["residual"]["bytes"]
    assert total == pytest.approx(rep["totals"]["bytes"])
    # the documented tolerance vs the compiled module's own totals
    recon = rep["reconciliation"]
    t = recon["flops_tolerance"]
    assert 1.0 / (1.0 + t) <= recon["flops_ratio"] <= 1.0 + t, recon
    t = recon["bytes_tolerance"]
    assert 1.0 / (1.0 + t) <= recon["bytes_ratio"] <= 1.0 + t, recon
    # content-addressed by the registry fingerprint, on disk
    assert rep["fingerprint"] in fx.reports_on_disk(str(tmp_path))


def test_capture_adds_zero_compiles_and_dispatches(tmp_path):
    """Acceptance: with capture enabled, steady-state training pays
    zero extra counted XLA compiles and zero extra host dispatches —
    the AOT capture compile rides the suppress fence, and capture runs
    once per fingerprint, never per step."""
    fx.configure(on=True, directory=str(tmp_path))
    mod, db = _mlp_module(seed=3)
    mod.forward_backward(db)
    mod.update()                         # warmup step captures here
    assert any(r["kind"] == "fused_step" for r in fx.reports().values())

    def counters():
        snap = tm.snapshot()
        fam = tm.REGISTRY._families.get("op/dispatch_total")
        disp = sum(c.value for lv, c in fam.series()
                   if lv and lv[0] == "fused_train_step")
        return snap["backend_compile_total"], disp

    compiles0, disp0 = counters()
    steps = 5
    for _ in range(steps):
        mod.forward_backward(db)
        mod.update()
    compiles1, disp1 = counters()
    assert compiles1 - compiles0 == 0
    assert disp1 - disp0 == steps        # exactly one dispatch per step


def test_unavailable_backend_degrades_to_stanza():
    """A capture failure (no jitted, no lowered) produces the
    documented n/a stanza + counter, never an exception."""
    fx.configure(on=True, directory=None)
    before = tm.snapshot().get("forensics_unavailable", 0)
    pkey = pg.ProgramKey("executor_forward", "g-broken", {"v": 1})
    rep = fx.maybe_capture(pkey, None, ())
    assert rep["unavailable"] is True
    assert "n/a" in rep["stanza"]
    assert tm.snapshot().get("forensics_unavailable", 0) == before + 1
    # the endpoint serves the stanza instead of erroring
    code, payload = fx.programs_endpoint("key=" + rep["fingerprint"])
    assert code == 200
    assert payload["forensics"]["unavailable"] is True


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------

def test_report_roundtrip_and_corrupt_file(tmp_path):
    ra, rb = _capture_pair(tmp_path)
    path = os.path.join(str(tmp_path), ra["fingerprint"] + ".json")
    assert os.path.exists(path)
    loaded = fx.load_report(path)
    assert loaded == ra
    # flip payload bytes inside the CRC frame: load must refuse
    with open(path, "r") as f:
        doc = json.load(f)
    doc["report"]["totals"]["bytes"] = -1
    with open(path, "w") as f:
        json.dump(doc, f)
    assert fx.load_report(path) is None
    # the fallback walk skips the torn file, keeps the good one
    walked = fx.reports_on_disk(str(tmp_path))
    assert ra["fingerprint"] not in walked
    assert rb["fingerprint"] in walked
    # a truncated file (torn write) is equally refused
    with open(path, "w") as f:
        f.write('{"format": 1, "crc32": 123')
    assert fx.load_report(path) is None


# ---------------------------------------------------------------------------
# diff
# ---------------------------------------------------------------------------

def test_diff_flags_real_fusion_change(tmp_path):
    ra, rb = _capture_pair(tmp_path)
    blackbox.configure(str(tmp_path / "flight.bin"))
    d = fx.diff(ra, rb)
    assert d["regressed"] is True and d["regressions"]
    # identical reports never regress
    clean = fx.diff(ra, ra, record=False)
    assert clean["regressed"] is False and not clean["regressions"]
    # the regression left a flight-recorder event with both sides
    events, _torn = blackbox.read_events()
    ev = [e for e in events if e["event"] == "forensics"]
    assert ev and ev[0]["a"] == ra["fingerprint"] \
        and ev[0]["b"] == rb["fingerprint"]


def test_diff_across_numerics_flag_change(tmp_path):
    """Acceptance: two captures of the SAME model under a real flag
    change (MXNET_NUMERICS off vs step) land as distinct
    content-addressed artifacts, and the diff flags the genuine fusion
    difference (the sentinel's in-program reductions)."""
    fx.configure(on=True, directory=str(tmp_path))
    prev = health.numerics_mode()
    try:
        health.set_numerics("off")
        mod, db = _mlp_module(seed=11)
        mod.forward_backward(db)
        mod.update()
        off = [r for r in fx.reports().values()
               if r["kind"] == "fused_step"]
        assert len(off) == 1
        health.set_numerics("step")
        mod, db = _mlp_module(seed=11)
        mod.forward_backward(db)
        mod.update()
        step = [r for r in fx.reports().values()
                if r["kind"] == "fused_step"
                and r["fingerprint"] != off[0]["fingerprint"]]
        assert len(step) == 1            # the flag re-keys the artifact
        d = fx.diff(off[0], step[0], record=False)
        assert d["regressed"] is True
        assert any("fusion count grew" in r or "bytes grew" in r
                   for r in d["regressions"])
    finally:
        health.set_numerics(prev)


def test_diff_unavailable_is_incomparable():
    fx.configure(on=True, directory=None)
    rep = fx.maybe_capture(
        pg.ProgramKey("executor_forward", "g-na", {"v": 1}), None, ())
    d = fx.diff(rep, rep, record=False)
    assert d["comparable"] is False and not d["regressions"]


# ---------------------------------------------------------------------------
# surfaces: /programs on both mounts, CLI
# ---------------------------------------------------------------------------

def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def test_programs_endpoint_on_telemetry_serve(tmp_path):
    fx.configure(on=True, directory=str(tmp_path))
    mod, db = _mlp_module(seed=5)
    mod.forward_backward(db)
    mod.update()
    fp = next(r["fingerprint"] for r in fx.reports().values()
              if r["kind"] == "fused_step")
    srv = tm.serve()
    try:
        code, body = _get_json(srv.url + "/programs")
        assert code == 200
        assert body["forensics"]["enabled"] is True
        assert body["forensics"]["captured"] >= 1
        assert body["programs"][fp]["forensics"] is True
        code, body = _get_json(srv.url + "/programs?key=" + fp)
        assert code == 200
        assert body["forensics"]["fusions_top"]
        assert body["forensics"]["reconciliation"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(srv.url + "/programs?key=deadbeef00")
        assert ei.value.code == 404
    finally:
        srv.close()


def test_programs_endpoint_on_serve_http(tmp_path):
    from mxnet_tpu.serve import InferenceEngine, ServeConfig, serve_http
    from mxnet_tpu.serving import Predictor
    fx.configure(on=True, directory=str(tmp_path))
    data = mx.sym.Variable("data")
    sym = mx.sym.softmax(
        mx.sym.FullyConnected(data, num_hidden=3, name="fc"), name="prob")
    rng = np.random.RandomState(0)
    path = str(tmp_path / "m.params")
    mx.nd.save(path, {
        "arg:fc_weight": mx.nd.array(rng.randn(3, 4).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(np.zeros(3, np.float32))})
    with open(path, "rb") as f:
        blob = f.read()
    pred = Predictor(sym.tojson(), blob, input_shapes={"data": (1, 4)})
    eng = InferenceEngine(pred, ServeConfig(max_batch=2, workers=1))
    eng.warmup()
    srv = serve_http(eng, port=0)
    try:
        code, body = _get_json(srv.url + "/programs")
        assert code == 200
        assert body["forensics"]["enabled"] is True
        assert body["count"] >= 1
    finally:
        srv.close()
        eng.close()


def test_cli_table_and_diff_exit_codes(tmp_path):
    ra, rb = _capture_pair(tmp_path)
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")

    def run(*args):
        return subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.forensics"] + list(args),
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO_ROOT)

    r = run(str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert ra["fingerprint"] in r.stdout and rb["fingerprint"] in r.stdout
    r = run(str(tmp_path / (ra["fingerprint"] + ".json")))
    assert r.returncode == 0 and "reconciliation" in r.stdout
    # regression diff exits 1 and names the regression in --json
    r = run("--json", "--diff", ra["fingerprint"], rb["fingerprint"],
            str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert json.loads(r.stdout.strip())["regressed"] is True
    # clean self-diff exits 0
    r = run("--diff", ra["fingerprint"], ra["fingerprint"], str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    # unknown fingerprint exits 2
    r = run("--diff", "ffffffff", ra["fingerprint"], str(tmp_path))
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# satellite: diagnostics join
# ---------------------------------------------------------------------------

def test_worst_fusions_in_diagnostics(tmp_path):
    fx.configure(on=True, directory=str(tmp_path))
    mod, db = _mlp_module(seed=7)
    mod.forward_backward(db)
    mod.update()
    worst = fx.worst_fusions(limit=3)
    assert worst and all(w["score"] >= 0 for w in worst)
    diag = mx.diagnostics(as_dict=True)
    assert diag["health"]["worst_fusions"]
