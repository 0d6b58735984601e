"""Unified compiled-program registry + persistent compile cache
(mxnet_tpu/programs.py; ISSUE 14).

Acceptance: a second ``InferenceEngine.warmup()`` of an 8-bucket ladder
in a FRESH process sharing the first one's compile cache performs ZERO
real backend compiles (telemetry-asserted via the disk-hit/compile
split) and serves outputs bitwise-identical to the cold-compiled
replica — ``test_cold_start_fresh_process`` (marked ``slow``: two
subprocess imports). The cheap in-process analogs — registry program
sharing across engines, the disk-hit/compile telemetry split, cache-key
correctness, salt/corruption safety rails, the cache-directory rule —
run in tier-1, all against ONE tiny shared ladder.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import programs as pg
from mxnet_tpu import telemetry as tm
from mxnet_tpu.serve import InferenceEngine, ServeConfig
from mxnet_tpu.serving import Predictor

FEATURE = 4
CLASSES = 3
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared fixtures: the session's cache dir (placed from outside by
# tests/conftest.py) + ONE tiny ladder for the whole module (tier-1 wall
# budget: every test here reuses these compiles)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def cache_dir():
    # the warm-set manifest is session-wide now: start from a registry
    # (and a seen-set) that says nothing about it
    pg.reset()
    return pg.cache_dir()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(symbol_json, param_bytes) for softmax(FC(data)) — the shared
    tiny ladder's model."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(3)
    path = str(tmp_path_factory.mktemp("model") / "m.params")
    mx.nd.save(path, {
        "arg:fc_weight": mx.nd.array(
            rng.randn(CLASSES, FEATURE).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(
            rng.randn(CLASSES).astype(np.float32))})
    with open(path, "rb") as f:
        blob = f.read()
    return sym.tojson(), blob


def _engine(model):
    sym_json, blob = model
    pred = Predictor(sym_json, blob, input_shapes={"data": (1, FEATURE)})
    return InferenceEngine(pred, ServeConfig(max_batch=2, workers=1))


@pytest.fixture(scope="module")
def warm_engine(model, cache_dir):
    """The shared warmed ladder (buckets 1, 2): compiled once, reused
    by every test in this module."""
    eng = _engine(model)
    eng.warmup()
    return eng


# ---------------------------------------------------------------------------
# the cache-directory rule: placed from outside, else one fixed
# in-checkout path; never set lazily, never a temp path
# ---------------------------------------------------------------------------

def _recorded_config_updates(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_from_outside_means_code_sets_none(monkeypatch):
    outside = os.environ["JAX_COMPILATION_CACHE_DIR"]   # tests/conftest.py
    assert pg.cache_dir() == os.path.abspath(outside)
    calls = _recorded_config_updates(monkeypatch)
    pg.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in [k for k, _ in calls]
    # the gates are zeroed either way: tiny eager programs are cached
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in calls
    assert ("jax_persistent_cache_min_entry_size_bytes", -1) in calls


def test_cache_dir_default_is_one_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    calls = _recorded_config_updates(monkeypatch)
    pg.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ("jax_compilation_cache_dir",
            os.path.join(repo, ".jax_cache")) in calls
    assert pg.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_code_path_sets_a_cache_dir():
    """configure_compile_cache (called once, at ``import mxnet_tpu``) is
    the only place the package names jax's cache-dir option."""
    pkg = os.path.dirname(os.path.abspath(pg.__file__))
    hits = []
    for root, _dirs, files in os.walk(pkg):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    src = f.read()
                if '"jax_compilation_cache_dir"' in src \
                        or "MXNET_COMPILE_CACHE_DIR" in src \
                        or "tempfile" in src and "compilation_cache" in src:
                    hits.append(os.path.relpath(path, pkg))
    assert hits == ["programs.py"]
    with open(os.path.join(pkg, "__init__.py")) as f:
        assert "configure_compile_cache()" in f.read()


# ---------------------------------------------------------------------------
# cache-key correctness
# ---------------------------------------------------------------------------

def test_fingerprint_cache_key_correctness():
    base = dict(kind="executor_forward", graph="g0",
                spec={"args": [["data", [1, 4], "float32"]],
                      "mesh": None, "donate": True, "numerics": "off"})

    def fp(**over):
        d = dict(base)
        d.update(over)
        return pg.ProgramKey(d["kind"], d["graph"], d["spec"],
                             d.get("instance")).fingerprint

    # identical key -> identical fingerprint (stable across calls)
    assert fp() == fp()
    # same graph at two shapes -> two entries
    assert fp(spec={"args": [["data", [2, 4], "float32"]],
                    "mesh": None, "donate": True,
                    "numerics": "off"}) != fp()
    # changed numerics mode / sharding / donation -> distinct keys
    for over in ({"numerics": "step"},
                 {"mesh": {"axes": {"dp": 2}, "batch": ["data"]}},
                 {"donate": False}):
        spec = dict(base["spec"])
        spec.update(over)
        assert fp(spec=spec) != fp()
    # graph and kind and instance all participate
    assert fp(graph="g1") != fp()
    assert fp(kind="fused_step") != fp()
    assert fp(instance="i:1") != fp()
    # the version salt is folded in: a different library/backend
    # version yields a different fingerprint for the same key
    old = pg._salt_cache[0]
    try:
        a = fp()
        pg._salt_cache[0] = "mxnet=other;jax=9.9.9"
        assert fp() != a
    finally:
        pg._salt_cache[0] = old


def test_get_or_build_registry_hit_and_eviction(monkeypatch):
    built = []

    def make(i):
        return pg.ProgramKey("test_evict", "gx", {"i": i})

    def build(i):
        built.append(i)
        return ("prog", i)

    monkeypatch.setenv("MXNET_PROGRAMS_MAX", "0")   # unbounded first
    assert pg.get_or_build(make(0), lambda: build(0)) == ("prog", 0)
    assert pg.get_or_build(make(0), lambda: build(0)) == ("prog", 0)
    assert built == [0]                  # second call: registry hit

    ev0 = tm.counter("programs/evictions_total").value
    monkeypatch.setenv("MXNET_PROGRAMS_MAX", "2")
    pg.reset()                           # start from a tiny registry
    for i in range(3):
        pg.get_or_build(make(i), lambda i=i: build(i))
    # LRU bound: 3 entries through a cap of 2 evicted the oldest
    assert pg.stats()["entries"] == 2
    assert tm.counter("programs/evictions_total").value > ev0
    assert built == [0, 0, 1, 2]
    # the evicted key rebuilds on next sight
    pg.get_or_build(make(0), lambda: build(0))
    assert built == [0, 0, 1, 2, 0]


def test_warm_twice_feedback():
    calls = []

    def fn(a, b):
        calls.append((a, b))
        return a + b

    out = pg.warm_twice(fn, (1, 2),
                        rebuild=lambda out, args: (out, args[1]))
    # two passes; the second fed the first pass's output (the donated
    # pjit-provenance discipline)
    assert calls == [(1, 2), (3, 2)]
    assert out == 5
    with pytest.raises(mx.base.MXNetError):
        pg.warm_twice(fn, (1, 2), passes=0)


# ---------------------------------------------------------------------------
# warm-set manifest: salt mismatch + corruption safety rails
# ---------------------------------------------------------------------------

def test_prewarm_skips_stale_salt_and_survives_corruption(cache_dir,
                                                          caplog):
    path = os.path.join(cache_dir, "warmset.json")
    pg.note_warm("test_site", "gp", {"bucket": 1})
    ent = pg.load_warmset(path)
    fp_ok = pg.fingerprint("test_site", "gp", {"bucket": 1})
    assert ent[fp_ok]["spec"] == {"bucket": 1}
    # doctor in an entry from a "different version" AND a valid-JSON
    # but non-dict entry (hand-edited/partially corrupted manifest)
    ent["deadbeef" * 4] = {"kind": "test_site", "graph": "gp",
                           "spec": {"bucket": 7},
                           "salt": "mxnet=other;jax=0.0.0"}
    ent["feedface" * 4] = "not-a-dict"
    with open(path, "w") as f:
        json.dump({"format": pg.WARMSET_FORMAT, "entries": ent}, f)

    replayed = []
    import logging
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.programs"):
        report = pg.prewarm(sites={"test_site": replayed.append},
                            graph="gp")
    # stale entry skipped WITH a warning, never replayed as a wrong
    # trace; the non-dict entry dropped (never a crash); the valid
    # entry replayed
    assert report["skipped_salt"] == 1
    assert any("stale salt" in r.message for r in caplog.records)
    assert any("non-dict" in r.message for r in caplog.records)
    assert replayed == [{"bucket": 1}]

    # version-salt skip is also counted
    assert tm.counter("programs/prewarm_skipped_total").value >= 1

    # corrupt/torn manifest -> clean fallback to the include set (a
    # cold compile), never a crash
    with open(path, "w") as f:
        f.write('{"format": 1, "entries": {"tor')
    corrupt0 = tm.counter("programs/warmset_corrupt_total").value
    replayed = []
    report = pg.prewarm(sites={"test_site": replayed.append},
                        include=[("test_site", {"bucket": 2})],
                        graph="gp")
    assert replayed == [{"bucket": 2}]
    assert report["replayed"] == 1
    assert tm.counter("programs/warmset_corrupt_total").value > corrupt0
    os.unlink(path)                      # leave a clean manifest behind

    # a MANIFEST entry whose replay raises is contained per entry
    # (one stale spec can't take down startup)...
    def boom(spec):
        raise RuntimeError("stale spec")

    pg.note_warm("test_site", "gp", {"bucket": 3})
    report = pg.prewarm(sites={"test_site": boom}, graph="gp")
    assert report["failed"] == 1
    # ...but a failure in the caller's own configured ladder RAISES —
    # never report a replica warm over a broken program
    with pytest.raises(RuntimeError):
        pg.prewarm(sites={"test_site": boom},
                   include=[("test_site", {"bucket": 3})],
                   use_manifest=False)
    # a replay callable may decline a spec with ``return False``
    report = pg.prewarm(sites={"test_site": lambda spec: False},
                        include=[("test_site", {"bucket": 3})],
                        use_manifest=False)
    assert report["rejected"] == 1 and report["replayed"] == 0
    os.unlink(path)                      # leave a clean manifest behind,
    pg.reset()                           # and no memory of the old one


# ---------------------------------------------------------------------------
# registry program sharing + the disk-hit/compile split (in-process
# analogs of the cold-start acceptance)
# ---------------------------------------------------------------------------

def test_engine_warmup_writes_warmset(warm_engine, cache_dir):
    ent = pg.load_warmset()
    kinds = {}
    for e in ent.values():
        kinds.setdefault(e["kind"], []).append(e)
    # one replayable serve_bucket entry per ladder bucket, with the
    # abstract input spec a future replica needs
    buckets = sorted(e["spec"]["bucket"] for e in kinds["serve_bucket"]
                     if e["graph"] == warm_engine._graph_hash)
    assert buckets == [1, 2]
    spec = next(e["spec"] for e in kinds["serve_bucket"]
                if e["spec"]["bucket"] == 2)
    assert spec["inputs"]["data"] == [[2, FEATURE], "float32"]
    # the executor-level programs registered too
    assert "executor_forward" in kinds
    assert warm_engine.warm_report["replayed"] >= 2


def test_second_engine_warmup_zero_compiles_in_process(model,
                                                       warm_engine):
    """A hot-swap replacement engine over the same model re-warms its
    whole ladder from the process-wide registry: ZERO new compile
    requests (not even disk loads)."""
    compiles0 = tm.snapshot()["backend_compile_total"]
    hits0 = tm.counter("programs/registry_hits_total").value
    eng = _engine(model)
    eng.warmup()
    assert eng.ready is False            # no workers started (ready
    assert eng._ready                    # gates on liveness), but warm
    assert tm.snapshot()["backend_compile_total"] == compiles0
    assert tm.counter("programs/registry_hits_total").value > hits0
    # outputs bitwise-identical to the first engine's programs (they
    # ARE the same programs)
    x = np.random.RandomState(5).randn(2, FEATURE).astype(np.float32)
    a = warm_engine._bucket_pred(2)._exe.forward(is_train=False, data=x)
    b = eng._bucket_pred(2)._exe.forward(is_train=False, data=x)
    assert np.array_equal(a[0].asnumpy(), b[0].asnumpy())


def test_disk_hit_vs_compile_split(cache_dir):
    """A fresh jit wrapper over an already-cached computation loads
    from disk: the trace-level counter still moves (zero-recompile
    assertions mean zero TRACES) while the real-compile counter does
    not."""
    import jax
    import jax.numpy as jnp

    # two DISTINCT function objects with identical bodies: the second
    # wrapper misses every in-memory cache (a fresh process's
    # situation) but lowers to the same HLO module, so it loads from
    # the persistent cache on disk
    f1 = lambda x: jnp.sin(x) @ jnp.cos(x).T * 3.25    # noqa: E731
    f2 = lambda x: jnp.sin(x) @ jnp.cos(x).T * 3.25    # noqa: E731

    x = np.ones((6, 5), np.float32)
    real0 = tm.counter("programs/compile_total").value
    disk0 = tm.counter("programs/disk_hits_total").value
    traces0 = tm.compile_count()
    np.asarray(jax.jit(f1)(x))           # cold: real compile, cached
    real1 = tm.counter("programs/compile_total").value
    disk1 = tm.counter("programs/disk_hits_total").value
    assert real1 == real0 + 1
    assert disk1 == disk0
    np.asarray(jax.jit(f2)(x))           # twin wrapper: disk load
    assert tm.counter("programs/compile_total").value == real1
    assert tm.counter("programs/disk_hits_total").value == disk1 + 1
    # BOTH were compile requests: the honest trace counter moved twice
    assert tm.compile_count() == traces0 + 2
    assert tm.disk_hit_count() >= 1
    # snapshot carries the split
    snap = tm.snapshot()
    assert snap["programs_compile_total"] == real1
    assert snap["programs_disk_hits"] == disk1 + 1


def test_stats_and_entries_surface():
    st = pg.stats()
    assert st["entries"] > 0
    assert st["cache_dir"] is not None
    rows = pg.entries()
    assert any(r["kind"] == "executor_forward" for r in rows.values())
    for r in rows.values():
        assert r["uses"] >= 1


# ---------------------------------------------------------------------------
# the acceptance: fresh-process replica cold start (slow: 2 subprocess
# imports + an 8-bucket ladder compile)
# ---------------------------------------------------------------------------

_COLD_START_DRIVER = r"""
import hashlib, json, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.serve import InferenceEngine, ServeConfig
from mxnet_tpu.serving import Predictor

params_path, max_batch = sys.argv[1], int(sys.argv[2])
data = mx.sym.Variable("data")
h = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
h = mx.sym.Activation(h, act_type="relu", name="relu1")
h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
sym = mx.sym.softmax(h, name="prob")
rng = np.random.RandomState(7)
mx.nd.save(params_path, {
    "arg:fc1_weight": mx.nd.array(
        (rng.randn(64, 784) * 0.1).astype(np.float32)),
    "arg:fc1_bias": mx.nd.array(np.zeros(64, np.float32)),
    "arg:fc2_weight": mx.nd.array(
        (rng.randn(10, 64) * 0.1).astype(np.float32)),
    "arg:fc2_bias": mx.nd.array(np.zeros(10, np.float32))})
with open(params_path, "rb") as f:
    blob = f.read()
pred = Predictor(sym.tojson(), blob, input_shapes={"data": (1, 784)})
eng = InferenceEngine(pred, ServeConfig(max_batch=max_batch, workers=1))
eng.warmup()
# bitwise probe: one fixed input through every bucket program
probe_rng = np.random.RandomState(11)
h = hashlib.md5()
for b in eng.config.buckets:
    x = probe_rng.randn(b, 784).astype(np.float32)
    outs = eng._bucket_pred(b)._exe.forward(is_train=False, data=x)
    h.update(outs[0].asnumpy().tobytes())
snap = tm.snapshot()
print("COLD_START " + json.dumps({
    "buckets": len(eng.config.buckets),
    "compiles": snap["programs_compile_total"],
    "disk_hits": snap["programs_disk_hits"],
    "probe_md5": h.hexdigest()}), flush=True)
"""


def _run_driver(source, args, env_extra, marker, timeout=600):
    """Run ``source`` in a FRESH python process from the repo root (so
    ``-c`` puts the checkout on sys.path and nothing is written outside
    it) and parse its ``marker``-prefixed JSON line."""
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", source] + list(args),
                       capture_output=True, text=True, timeout=timeout,
                       cwd=REPO_ROOT, env=env)
    for line in reversed((r.stdout or "").splitlines()):
        if line.startswith(marker + " "):
            return json.loads(line[len(marker) + 1:])
    raise RuntimeError(
        "driver produced no %s line (rc %d): %s" % (
            marker, r.returncode, (r.stderr or "")[-800:]))


@pytest.mark.slow
def test_cold_start_fresh_process(tmp_path):
    """Two FRESH processes each build + warm an 8-bucket MLP serve
    ladder against one shared ``JAX_COMPILATION_CACHE_DIR``: the first
    compiles and fills the cache and the warm-set manifest; the second's
    warmup does ZERO real backend compiles (all disk hits) and its
    outputs are bitwise the cold-compiled replica's."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "MXNET_TELEMETRY": "1"}
    args = [str(tmp_path / "m.params"), "128"]
    cold = _run_driver(_COLD_START_DRIVER, args, env, "COLD_START")
    warm = _run_driver(_COLD_START_DRIVER, args, env, "COLD_START")
    assert cold["buckets"] == 8 and cold["compiles"] > 0
    assert warm["compiles"] == 0
    assert warm["disk_hits"] > 0
    assert warm["probe_md5"] == cold["probe_md5"]
