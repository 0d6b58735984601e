"""Elastic pod training (ISSUE 19): checkpoint-free rescale of
``dist_tpu_sync`` on membership change.

Tier-1 units cover the pieces in isolation: the microbatch ownership
plan, the step watchdog, the file-based rescale barrier (vote
agreement, loss detection, join admission), the bitwise input reshard,
the grad-accumulated fused step's bitwise equivalence to the unfused
reference, the supervisor's relaunch-as-joiner env hook, and the
env-knob docs lint.

The ``slow``-marked chaos acceptance runs the real thing: a 2-process
gloo fit whose rank 1 is SIGKILLed mid-step by an armed fault, the
survivor rescales to world 1 without a checkpoint, the victim
relaunches as a joiner and the mesh grows back — with the whole
per-step parameter trajectory compared bitwise against a never-faulted
twin run (params are a deterministic function of nothing but the
trajectory, so digest equality at every step IS loss-trace equality).
"""
import hashlib
import importlib.util
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, elastic, io
from mxnet_tpu import optimizer as opt
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# plan_microbatches: part ownership after a rescale
# ---------------------------------------------------------------------------

def test_plan_microbatches_ownership():
    # full world: one part each, no accumulation
    assert elastic.plan_microbatches(4, 4, 3) == (1, (3,))
    # half the world: member j adopts parts [j, j+W, ...]
    assert elastic.plan_microbatches(4, 2, 0) == (2, (0, 2))
    assert elastic.plan_microbatches(4, 2, 1) == (2, (1, 3))
    # last survivor owns everything, in base-rank order per microbatch
    assert elastic.plan_microbatches(4, 1, 0) == (4, (0, 1, 2, 3))
    # the owned sets tile the base world exactly (microbatch a covers
    # parts [a*W, (a+1)*W) across the membership)
    _, o0 = elastic.plan_microbatches(6, 2, 0)
    _, o1 = elastic.plan_microbatches(6, 2, 1)
    assert sorted(o0 + o1) == list(range(6))


def test_plan_microbatches_rejects_uneven_split():
    with pytest.raises(MXNetError, match="divide"):
        elastic.plan_microbatches(4, 3, 0)


# ---------------------------------------------------------------------------
# call_bounded: the step watchdog
# ---------------------------------------------------------------------------

def test_call_bounded_passthrough_and_stall():
    assert elastic.call_bounded(lambda: 7, 5.0) == 7
    # timeout <= 0 disables the watchdog (direct call, no thread)
    assert elastic.call_bounded(lambda: 7, 0) == 7

    def _boom():
        raise ValueError("inner")

    with pytest.raises(ValueError, match="inner"):
        elastic.call_bounded(_boom, 5.0)
    with pytest.raises(elastic.StepStallError, match="unit step"):
        elastic.call_bounded(lambda: time.sleep(10), 0.2, what="unit step")


# ---------------------------------------------------------------------------
# ElasticAgent: the file-based rescale barrier
# ---------------------------------------------------------------------------

def _agent(tmp_path, **kw):
    kw.setdefault("dead_s", 5.0)
    kw.setdefault("hb_s", 0.1)
    return elastic.ElasticAgent(root=str(tmp_path), **kw)


def test_rescale_barrier_agrees_min_step(tmp_path):
    """Two live survivors vote different last-completed steps (at most
    one step apart under BSP); the plan takes the minimum — the last
    GLOBALLY completed step."""
    a0 = _agent(tmp_path, rank=0, world=2).start()
    a1 = _agent(tmp_path, rank=1, world=2).start()
    a0.completed(1, 7)
    a1.completed(1, 8)        # had the in-flight step locally completed
    plans = {}
    t = threading.Thread(
        target=lambda: plans.update(
            p1=a1.rescale(admit_joiners=False, timeout=20)))
    t.start()
    plans["p0"] = a0.rescale(admit_joiners=False, timeout=20)
    t.join(30)
    a0.stop()
    a1.stop()
    assert not t.is_alive()
    assert plans["p0"]["step"] == [1, 7]
    assert plans["p1"]["step"] == [1, 7]
    assert plans["p0"]["world"] == 2
    # both adopted the next generation with ranks preserved
    assert (a0.gen, a1.gen) == (2, 2)
    assert (a0.rank, a1.rank) == (0, 1)
    assert (a0.step, a1.step) == ((1, 7), (1, 7))


def test_rescale_shrinks_over_lost_rank(tmp_path):
    """A stale heartbeat marks the rank lost; the surviving rank
    coordinates a world-1 plan carrying its own vote."""
    a0 = _agent(tmp_path, rank=0, world=2, dead_s=0.5).start()
    stale = {"rank": 1, "pid": 0, "host": "127.0.0.1", "step": [0, 9],
             "ts": time.time() - 60.0}
    (tmp_path / "hb-g1-r1.json").write_text(json.dumps(stale))
    lost = a0.lost()
    assert list(lost) == [1] and lost[1] > 0.5
    a0.completed(0, 3)
    plan = a0.rescale(admit_joiners=False, timeout=20)
    a0.stop()
    assert plan["world"] == 1
    assert plan["step"] == [0, 3]
    assert plan["grow"] is False
    assert a0.rank == 0 and a0.world == 1 and a0.gen == 2


def test_join_admission_grows_world(tmp_path):
    """A joiner files a request, the running world admits it at the
    barrier: world grows, the joiner gets the next rank and the
    survivors' agreed step (joiners have no vote)."""
    a0 = _agent(tmp_path, rank=0, world=1, base_world=2).start()
    a0.completed(2, 5)
    j = _agent(tmp_path)
    j.request_join()
    deadline = time.time() + 10
    while not a0.joiners() and time.time() < deadline:
        time.sleep(0.05)
    assert j.nonce in a0.joiners()
    box = {}
    t = threading.Thread(target=lambda: box.update(p=j.wait_plan(timeout=20)))
    t.start()
    plan = a0.rescale(admit_joiners=True, timeout=20)
    t.join(30)
    a0.stop()
    j.stop()
    assert not t.is_alive()
    assert plan["world"] == 2 and plan["grow"] is True
    assert plan["step"] == [2, 5]
    assert box["p"]["gen"] == plan["gen"] == 2
    assert j.rank == 1 and j.world == 2 and j.base_world == 2
    # admission consumed the join request
    assert a0.joiners() == {}


# ---------------------------------------------------------------------------
# NDArrayIter.elastic_reshard: bitwise input adoption
# ---------------------------------------------------------------------------

def test_elastic_reshard_bitwise():
    """A survivor adopting dead ranks' parts feeds, microbatch by
    microbatch, EXACTLY the rows those ranks would have fed — across
    epochs (reshuffles), after a mid-epoch seek, through a cursor
    round-trip into a fresh iterator, and back after a grow."""
    N, D, B, L = 64, 5, 4, 4      # base world 4, per-rank batch 4
    rng = np.random.RandomState(0)
    X = rng.uniform(size=(N, D)).astype(np.float32)
    Y = np.arange(N).astype(np.float32)

    def base_iter(r):
        return io.NDArrayIter(X, Y, batch_size=L, shuffle=True, seed=77,
                              last_batch_handle="discard", num_parts=B,
                              part_index=r)

    nb = (N // B) // L
    feed = {}                     # (epoch, t, base_rank) -> (data, label)
    for r in range(B):
        it = base_iter(r)
        for e in range(2):
            if e:
                it.reset()
            for t in range(nb):
                b = next(it)
                feed[(e, t, r)] = (b.data[0].asnumpy().copy(),
                                   b.label[0].asnumpy().copy())

    W, j = 2, 1                   # ranks 0 and 2 died; rank 1 -> new rank 1
    accum, owned = elastic.plan_microbatches(B, W, j)
    assert (accum, owned) == (2, (1, 3))

    surv = base_iter(j)
    surv.elastic_reshard(B, owned)
    surv.restore_state({"epoch": 0, "batch": 0})
    for e in range(2):
        if e:
            surv.reset()
        for t in range(nb):
            b = next(surv)
            d, lab = b.data[0].asnumpy(), b.label[0].asnumpy()
            assert d.shape == (accum * L, D)
            for a in range(accum):
                want_d, want_l = feed[(e, t, owned[a])]
                assert np.array_equal(d[a * L:(a + 1) * L], want_d)
                assert np.array_equal(lab[a * L:(a + 1) * L], want_l)

    # mid-epoch seek to the agreed step (epoch 1, batch 1)
    surv2 = base_iter(j)
    surv2.elastic_reshard(B, owned)
    surv2.restore_state({"epoch": 1, "batch": 1})
    d = next(surv2).data[0].asnumpy()
    assert all(np.array_equal(d[a * L:(a + 1) * L],
                              feed[(1, 1, owned[a])][0])
               for a in range(accum))

    # cursor round-trip through a fresh iterator (the relaunch path)
    cur = surv2.checkpoint_state(epoch=1, nbatch=2)
    fresh = base_iter(j)
    fresh.restore_state(cur)
    d = next(fresh).data[0].asnumpy()
    assert all(np.array_equal(d[a * L:(a + 1) * L],
                              feed[(1, 2, owned[a])][0])
               for a in range(accum))

    # grow back to the full world: A=1, original part again
    _, owned1 = elastic.plan_microbatches(B, B, j)
    surv2.elastic_reshard(B, owned1)
    surv2.restore_state({"epoch": 1, "batch": 3})
    assert np.array_equal(next(surv2).data[0].asnumpy(),
                          feed[(1, 3, j)][0])
    assert surv2.batch_size == L


# ---------------------------------------------------------------------------
# grad-accumulated fused step: bitwise vs the unfused reference
# ---------------------------------------------------------------------------

def _mlp_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _make_module(batch, dim, seed=11):
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (batch, dim))],
             label_shapes=[("softmax_label", (batch,))])
    rng = np.random.RandomState(seed)
    args = {}
    for name, arr in sorted(mod._exec.arg_dict.items()):
        if name in ("data", "softmax_label"):
            continue
        args[name] = mx.nd.array(
            rng.uniform(-0.1, 0.1, arr.shape).astype(np.float32))
    mod.init_params(arg_params=args, aux_params={}, force_init=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    return mod


def test_grad_accum_fused_step_bitwise():
    """The elastic rescale's fused step with ``accum_feed`` (A
    sequential microbatches, summed grads, ONE rule application) is
    bitwise-identical to the manual reference: per-microbatch
    forward/backward on the unfused path, host-side grad sum, one
    eager rule application — the property that makes a shrunk world's
    updates match the base world's."""
    import jax.numpy as jnp

    A, L, DIM = 2, 8, 16
    rng = np.random.RandomState(3)
    data = rng.uniform(-1, 1, (A * L, DIM)).astype(np.float32)
    label = rng.randint(0, 10, (A * L,)).astype(np.float32)

    # reference: unfused microbatch loop + one rule application
    ref = _make_module(batch=L, dim=DIM)
    # a monitor callback forces the unfused path, so grad_dict
    # materializes per microbatch
    ref._exec._monitor_callback = lambda *a: None
    g_tot = None
    for a in range(A):
        b = io.DataBatch(data=[mx.nd.array(data[a * L:(a + 1) * L])],
                         label=[mx.nd.array(label[a * L:(a + 1) * L])])
        ref.forward(b, is_train=True)
        ref.backward()
        g = {k: v.asnumpy().copy() for k, v in ref._exec.grad_dict.items()
             if v is not None}
        g_tot = g if g_tot is None else {k: g_tot[k] + g[k] for k in g_tot}
    rule = ref._optimizer.fused_rule()
    want = {}
    for i, name in enumerate(ref._param_names):
        w = ref._exec.arg_dict[name]
        st = opt.fused_state_arrays(ref._updater.ensure_state(i, w))
        neww, _ = rule(jnp.asarray(w.asnumpy()),
                       jnp.asarray(g_tot[name]),
                       tuple(jnp.asarray(s.asnumpy()) for s in st),
                       ref._optimizer.fused_hyper(i))
        want[name] = np.asarray(neww)

    # fused accum step: one dispatch over the stacked microbatches
    mod = _make_module(batch=L, dim=DIM)
    exe = mod._exec
    update_names, states, hyper = [], {}, {}
    for i, name in enumerate(mod._param_names):
        if exe._grad_req.get(name, "null") == "null":
            continue
        w = exe.arg_dict[name]
        update_names.append(name)
        states[name] = opt.fused_state_arrays(
            mod._updater.ensure_state(i, w))
        hyper[name] = mod._optimizer.fused_hyper(i)
    exe.train_step(mod._optimizer.fused_rule(), tuple(update_names),
                   states, hyper,
                   accum_feed={"data": data.reshape(A, L, DIM),
                               "softmax_label": label.reshape(A, L)})

    for name in update_names:
        got = np.asarray(exe.arg_dict[name].asnumpy())
        assert np.array_equal(got, want[name]), (
            "%s drifted: maxdiff=%g"
            % (name, np.max(np.abs(got - want[name]))))


# ---------------------------------------------------------------------------
# ProcessSupervisor env hook: relaunch-as-joiner
# ---------------------------------------------------------------------------

def test_elastic_rejoin_env_hook():
    hook = checkpoint.elastic_rejoin_env("/nfs/el")
    assert hook(0, {}) == {}              # first launch: env untouched
    ov = hook(2, {})
    assert ov["MXNET_ELASTIC_JOIN"] == "1"
    assert ov["MXNET_ELASTIC_DIR"] == "/nfs/el"
    for k in ("MXNET_DIST_COORDINATOR", "MXNET_DIST_NUM_PROCESSES",
              "MXNET_DIST_PROCESS_ID"):
        assert ov[k] is None              # None deletes the var


def test_supervisor_relaunches_as_joiner(monkeypatch):
    """A preempted elastic worker comes back with join-mode env: the
    stale pre-failure coordinates are dropped (after a rescale they
    may belong to a live peer)."""
    calls = []

    def fake_call(cmd, env=None, cwd=None):
        calls.append(dict(env))
        return 137 if len(calls) == 1 else 0

    monkeypatch.setattr(subprocess, "call", fake_call)
    sup = checkpoint.ProcessSupervisor(
        max_failures=3, relaunch_delay_s=0,
        env_hook=checkpoint.elastic_rejoin_env("/nfs/el"))
    base = {"MXNET_DIST_COORDINATOR": "h:1",
            "MXNET_DIST_NUM_PROCESSES": "2",
            "MXNET_DIST_PROCESS_ID": "1", "PATH": "/bin"}
    rc = sup.run(["train"], env=dict(base))
    assert rc == 0 and len(calls) == 2 and sup.launches == 2
    assert calls[0] == base               # launch 0: verbatim
    rejoin = calls[1]
    assert rejoin["MXNET_ELASTIC_JOIN"] == "1"
    assert rejoin["MXNET_ELASTIC_DIR"] == "/nfs/el"
    assert rejoin["PATH"] == "/bin"
    for k in ("MXNET_DIST_COORDINATOR", "MXNET_DIST_NUM_PROCESSES",
              "MXNET_DIST_PROCESS_ID"):
        assert k not in rejoin


# ---------------------------------------------------------------------------
# env-knob docs lint (tools/check_env_docs.py)
# ---------------------------------------------------------------------------

def test_env_docs_in_sync():
    """Every MXNET_* literal in code is a declared config.py knob,
    every doc token names one, and marker-scoped docs table every knob
    under their promised prefixes."""
    path = os.path.join(ROOT, "tools", "check_env_docs.py")
    spec = importlib.util.spec_from_file_location("check_env_docs", path)
    modl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(modl)
    keys = modl.registry_keys()
    assert "MXNET_ELASTIC_DIR" in keys and len(keys) > 50
    assert modl.run() == []


# ---------------------------------------------------------------------------
# chaos acceptance: SIGKILL a rank mid-step, compare against the twin
# ---------------------------------------------------------------------------

_CHAOS_WORKER = r'''
"""test_elastic chaos worker: one rank of a 2-process elastic fit.

Appends a sha256 digest of every parameter after EVERY completed step
to the report — the bitwise ledger the test compares across the
faulted survivor, the relaunched joiner, and the never-faulted twin.
"""
import hashlib, json, os, sys, time
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
from mxnet_tpu import telemetry as tm
from mxnet_tpu import dist_runtime
from mxnet_tpu import elastic as el
from mxnet_tpu.module import Module
if not joiner:
    # a joiner's runtime comes up inside ElasticFit.join against the
    # plan's coordinator, never the stale pre-failure env
    dist_runtime.acquire()

rescales = []
_orig_handle = el.ElasticFit.handle
def _timed_handle(self, exc):
    out = _orig_handle(self, exc)
    rescales.append({"t": time.perf_counter(), "resume": list(out),
                     "world_after": jax.process_count()})
    return out
el.ElasticFit.handle = _timed_handle

net = mx.sym.Variable("data")
net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
net = mx.sym.Activation(net, name="relu1", act_type="relu")
net = mx.sym.FullyConnected(net, name="fcout", num_hidden=10)
net = mx.sym.SoftmaxOutput(net, name="softmax")

# explicit seeded init: the twin comparison needs params identical
# ACROSS RUNS, not just across ranks (the kv init broadcast only
# gives the latter). A joiner must NOT build these: its params come
# from the broadcast, and touching devices before ElasticFit.join
# brings the runtime up would init the gloo backend with no client.
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

mod = Module(net, context=mx.cpu())
digests = {}
replay_mismatch = []
steps_log = []

def _digest():
    h = hashlib.sha256()
    for n in sorted(mod._param_names):
        a = mod._exec.arg_dict[n].asnumpy()
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()

def _cb(param):
    key = "%d:%d" % (param.epoch, param.nbatch)
    d = _digest()
    if key in digests and digests[key] != d:
        replay_mismatch.append(key)   # a replayed step MUST reproduce
    digests[key] = d
    steps_log.append({"t": time.perf_counter(), "epoch": param.epoch,
                      "compiles": tm.snapshot()["programs_compile_total"]})
    if pace_s:
        # paced so the relaunched joiner (a fresh interpreter + jax
        # import away) gets admitted before the survivor runs dry
        time.sleep(pace_s)

mod.fit(it, num_epoch=epochs, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        arg_params=arg_params, kvstore="dist_tpu_sync",
        batch_end_callback=_cb)

rep = {"rank": rank, "world_end": jax.process_count(),
       "steps_completed": len(steps_log),
       "replay_mismatch": replay_mismatch,
       "digests": digests, "rescales": []}
for i, r in enumerate(rescales):
    nxt = rescales[i + 1]["t"] if i + 1 < len(rescales) else float("inf")
    pre = [s for s in steps_log if s["t"] <= r["t"]]
    post = [s for s in steps_log if r["t"] < s["t"] <= nxt]
    e = {"world_after": r["world_after"], "resume": r["resume"]}
    if post:
        # step 1 after a rescale is the replay window (the new world's
        # program comes up there); from step 2 on, zero new traces
        # within the resume epoch (the NEXT epoch boundary builds the
        # world's one-time boundary program set — the twin pays the
        # same, asserted via steady_compiles below)
        e["first_step_compiles"] = (
            post[0]["compiles"] - (pre[-1]["compiles"] if pre else 0))
        same_epoch = [s for s in post if s["epoch"] == post[0]["epoch"]]
        e["compiles_after_first_step"] = (
            same_epoch[-1]["compiles"] - same_epoch[0]["compiles"])
    rep["rescales"].append(e)
# steady state: from two epochs past the last rescale (one epoch for
# the remainder of the resume epoch, one for the new world's first
# epoch boundary), NOTHING compiles — boundaries included
floor_epoch = (rescales[-1]["resume"][0] if rescales else 0) + 2
before = [s for s in steps_log if s["epoch"] < floor_epoch]
rep["steady_from_epoch"] = floor_epoch
rep["steady_compiles"] = (
    steps_log[-1]["compiles"] - before[-1]["compiles"]
    if before and steps_log[-1]["epoch"] >= floor_epoch else None)
print("CHAOS_REPORT " + json.dumps(rep), flush=True)
mod._kvstore.close()
dist_runtime.release()
'''

_EPOCHS, _NB, _L, _DIM = 4, 15, 4, 16


def _chaos_env(eldir, flight=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               MXNET_FUSED_STEP="1", MXNET_ELASTIC_DIR=eldir,
               MXNET_ELASTIC_HB_S="0.2", MXNET_DIST_DEAD_S="2.0",
               MXNET_STEP_TIMEOUT_S="60", ELASTIC_TEST_PACE_S="0.25")
    # jaxlib's CPU gloo path has segfaulted deserializing a donated
    # collective program from the persistent compile cache, so it
    # stays off here
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    for v in ("MXNET_TPU_PS_URI", "MXNET_FAULT_INJECT",
              "MXNET_ELASTIC_JOIN", "MXNET_FLIGHT_RECORDER"):
        env.pop(v, None)
    if flight:
        env["MXNET_FLIGHT_RECORDER"] = flight
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


def _report(out, who):
    for line in reversed(out.splitlines()):
        if line.startswith("CHAOS_REPORT "):
            return json.loads(line[len("CHAOS_REPORT "):])
    raise AssertionError("%s produced no CHAOS_REPORT: %s"
                         % (who, out[-1500:]))


@pytest.mark.slow
def test_chaos_sigkill_rescale_bitwise_vs_twin(tmp_path):
    """The ISSUE 19 acceptance: rank 1 of a 2-process gloo fit is
    SIGKILLed at the top of its 4th step (``dist.member:4:crash``);
    the survivor rescales to world 1 WITHOUT a checkpoint and keeps
    training; the victim relaunches as a joiner and the mesh grows
    back to 2. The survivor's per-step parameter digests — before the
    fault, through the shrink, and after the grow — are bitwise-equal
    to a never-faulted twin's at every step, and no step after a
    rescale's first (the replay window) compiles anything."""
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_CHAOS_WORKER)

    # --- twin: same code path (elastic enabled), nobody dies ---------
    el_twin = str(tmp_path / "el_twin")
    os.makedirs(el_twin)
    env = _chaos_env(el_twin)
    t0 = _spawn(script, 0, env, {})
    t1 = _spawn(script, 1, env, {})
    out0 = t0.communicate(timeout=600)[0]
    out1 = t1.communicate(timeout=600)[0]
    assert t0.returncode == 0, out0[-1500:]
    assert t1.returncode == 0, out1[-1500:]
    twin = _report(out0, "twin rank 0")
    assert twin["rescales"] == [] and twin["world_end"] == 2
    assert twin["steps_completed"] == _EPOCHS * _NB
    assert twin["steady_compiles"] == 0, twin

    # --- faulted run -------------------------------------------------
    el_dir = str(tmp_path / "el")
    os.makedirs(el_dir)
    flight = str(tmp_path / "flight-r0.bin")
    env = _chaos_env(el_dir, flight=flight)
    survivor = _spawn(script, 0, env, {})
    victim = _spawn(script, 1, env,
                    {"MXNET_FAULT_INJECT": "dist.member:4:crash"})
    procs = [survivor, victim]
    try:
        outv = victim.communicate(timeout=600)[0]
        assert victim.returncode in (137, -9), (
            "victim should die SIGKILL-grade at the armed fault, "
            "got rc=%r: %s" % (victim.returncode, outv[-1500:]))
        # wait for the shrink plan before relaunching, so the joiner
        # is a distinct grow rescale rather than folded into the loss
        # barrier (valid too, but not what this test asserts)
        deadline = time.time() + 120
        while (not [n for n in os.listdir(el_dir)
                    if n.startswith("plan-g")]
               and time.time() < deadline):
            time.sleep(0.1)
        rejoin = _spawn(script, 1, env, {"MXNET_ELASTIC_JOIN": "1"})
        procs.append(rejoin)
        outj = rejoin.communicate(timeout=600)[0]
        assert rejoin.returncode == 0, (
            "relaunched joiner failed rc=%r: %s"
            % (rejoin.returncode, outj[-1500:]))
        outs = survivor.communicate(timeout=600)[0]
        assert survivor.returncode == 0, (
            "survivor failed rc=%r: %s"
            % (survivor.returncode, outs[-1500:]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    surv = _report(outs, "survivor")
    join = _report(outj, "joiner")

    # shrink to 1, grow back to 2; training ends at full strength
    assert [r["world_after"] for r in surv["rescales"]] == [1, 2], surv
    assert surv["world_end"] == 2 and join["world_end"] == 2
    assert surv["steps_completed"] >= _EPOCHS * _NB

    # zero recompiles after each rescale's first step (replay window),
    # and total silence once past the last rescale's epoch + the new
    # world's one-time epoch-boundary builds (same as the twin's)
    for r in surv["rescales"]:
        assert r.get("compiles_after_first_step", 0) == 0, surv["rescales"]
    # None only if the grow landed so late no steady epochs remain (a
    # loaded machine); the twin's steady assert above still holds then
    assert surv["steady_compiles"] in (0, None), (
        surv["steady_from_epoch"], surv["rescales"])

    # in-run replay determinism: a re-run step reproduced its digest
    assert surv["replay_mismatch"] == []

    # THE bitwise contract: every step the survivor completed has the
    # same parameter digest as the unfaulted twin's — the loss trace
    # continues as if nothing died, and the final params match
    assert set(surv["digests"]) == set(twin["digests"])
    diverged = [k for k in twin["digests"]
                if surv["digests"][k] != twin["digests"][k]]
    assert diverged == [], "diverged at steps %s" % diverged[:5]

    # the joiner (params via kv broadcast, optimizer state via the
    # plan's blob) continues the same trajectory bitwise
    assert join["digests"], "joiner completed no steps"
    j_diverged = [k for k, v in join["digests"].items()
                  if twin["digests"].get(k) != v]
    assert j_diverged == [], "joiner diverged at %s" % j_diverged[:5]

    # flight recorder: the loss and both rescales are on disk
    from mxnet_tpu import blackbox
    events, _torn = blackbox.read_events(flight)
    names = [e["event"] for e in events]
    assert "member_lost" in names
    rescale_evs = [e for e in events if e["event"] == "rescale"]
    assert len(rescale_evs) == 2
    assert (rescale_evs[0]["old_world"], rescale_evs[0]["world"]) == (2, 1)
    assert rescale_evs[0]["grow"] is False
    assert (rescale_evs[1]["old_world"], rescale_evs[1]["world"]) == (1, 2)
    assert rescale_evs[1]["grow"] is True
