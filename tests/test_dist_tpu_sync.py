"""In-program pod collectives: ``dist_tpu_sync``.

The tentpole contract (ROADMAP item 2): a ``fit(kvstore="dist_tpu_sync")``
across 2 REAL processes (gloo CPU collectives, the multi-host route
``tests/test_kvstore_multiprocess.py`` established) trains with the
gradient all-reduce folded INTO the fused train-step program — one
``fused_train_step`` dispatch per step, zero XLA recompiles after step 2
(pjit provenance: the donated loop re-specializes once AT step 2), zero
bytes through any socket — and the final params are bitwise-identical
across ranks AND to single-process training on the concatenated data
(a 2-device local dp mesh: the same GSPMD partitioning, so the only
difference is which links carry the psum).

Single-process satellites: the ``fused_step_supported`` dist fallback is
gone for this type, ``_create_kvstore`` degrades to the local fused path
with a warning when no cluster exists, the program-registry version salt
names the process count, and ``io.dist_parts`` wires per-host sharding.
"""
import json
import os
import socket
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio
from mxnet_tpu import programs as pg
from mxnet_tpu import telemetry as tm
from mxnet_tpu.model import (_create_kvstore, _initialize_kvstore,
                             fused_step_supported)
from mxnet_tpu.module import Module

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# probe model shared by the 2-process workers and the in-parent twin:
# sizes, data, and initial params must be byte-identical everywhere
DIM, HIDDEN, CLASSES = 16, (32, 16), 10
SAMPLES, LOCAL_BATCH, WORKERS = 40, 4, 2


def _mlp_sym():
    net = mx.sym.Variable("data")
    for i, h in enumerate(HIDDEN):
        net = mx.sym.FullyConnected(net, name="fc%d" % (i + 1),
                                    num_hidden=h)
        net = mx.sym.Activation(net, name="relu%d" % (i + 1),
                                act_type="relu")
    net = mx.sym.FullyConnected(net, name="fcout", num_hidden=CLASSES)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _probe_data():
    rng = np.random.RandomState(3)
    X = rng.randn(SAMPLES, DIM).astype(np.float32)
    Y = rng.randint(0, CLASSES, SAMPLES).astype(np.float32)
    return X, Y


def _probe_params(mod):
    rng = np.random.RandomState(11)
    return {n: mx.nd.array(rng.randn(*a.shape).astype(np.float32) * 0.1)
            for n, a in sorted(mod._exec.arg_dict.items())
            if n not in ("data", "softmax_label")}


def _fit(mod, it, kvstore, arg_params, batch_cb=None):
    mod.fit(it, kvstore=kvstore, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "lr_scheduler":
                                  mx.lr_scheduler.FactorScheduler(
                                      step=1, factor=0.9)},
            arg_params=arg_params, aux_params={},
            batch_end_callback=batch_cb, num_epoch=1)
    return {n: v.asnumpy() for n, v in mod.get_params()[0].items()}


def _cpu_collectives_available():
    """Live-probed gloo gate (PR 7): the raw CPU backend cannot run
    multiprocess computations.  The knob is RESTORED after probing —
    this parent process also runs the single-process twin, and a CPU
    backend initialized with gloo selected but no distributed client
    fails outright."""
    import jax
    name = "jax_cpu_collectives_implementation"
    try:
        prev = jax.config.read(name)
        jax.config.update(name, "gloo")
        jax.config.update(name, prev)
        return True
    except (AttributeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# single-process satellites (fast tier-1)
# ---------------------------------------------------------------------------

def test_fused_step_supported_keeps_dist_tpu_sync():
    """The dist fallback is GONE for dist_tpu_sync — its allreduce is
    in-program — while socket dist types still take the unfused path."""
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    kv = mx.kvstore.create("dist_tpu_sync")
    try:
        assert fused_step_supported(opt, kv, update_on_kvstore=False)
        assert not fused_step_supported(opt, kv, update_on_kvstore=True)
    finally:
        kv.close()
    for socket_type in ("dist_sync", "dist_async", "dist_device_sync"):
        kv = mx.kvstore.create(socket_type)
        try:
            assert not fused_step_supported(opt, kv,
                                            update_on_kvstore=False), \
                socket_type
        finally:
            kv.close()


def test_create_kvstore_degrades_without_cluster(monkeypatch):
    """dist_tpu_sync with no live jax.distributed runtime and nothing
    to start one from trains on the LOCAL fused path with a warning —
    it must not demand a rendezvous that can never complete."""
    monkeypatch.delenv("MXNET_DIST_COORDINATOR", raising=False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        kv, update_on_kvstore = _create_kvstore("dist_tpu_sync", 1, {})
    assert kv is None and update_on_kvstore is False
    assert any("dist_tpu_sync" in str(x.message) for x in w)
    # multi-device single process: the local device store (the fused
    # path still updates locally)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        kv, update_on_kvstore = _create_kvstore("dist_tpu_sync", 2, {})
    assert kv is not None and kv.type == "device"
    assert update_on_kvstore is False


def test_single_process_dist_tpu_sync_fit_runs_fused(monkeypatch):
    """End-to-end degrade: fit(kvstore='dist_tpu_sync') on one host
    without a cluster trains on the fused single-program path."""
    monkeypatch.delenv("MXNET_DIST_COORDINATOR", raising=False)
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    X, Y = _probe_data()
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, DIM))],
             label_shapes=[("softmax_label", (8,))])
    args = _probe_params(mod)      # deterministic init shared with workers
    it = mio.NDArrayIter(X, Y, batch_size=8, shuffle=False)
    before = tm.snapshot()["fused_step_total"]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _fit(mod, it, "dist_tpu_sync", args)
    assert any("dist_tpu_sync" in str(x.message) for x in w)
    assert tm.snapshot()["fused_step_total"] - before == SAMPLES // 8


def test_version_salt_names_process_count():
    """2 processes x 1 device and 1 process x 2 devices share a device
    count; the registry salt must still tell them apart (a worker must
    never replay a single-host warm-set entry)."""
    assert "processes=1" in pg.version_salt()


def test_dist_parts_single_process():
    parts, index = mio.dist_parts()
    assert (parts, index) == (1, 0)
    snap = tm.REGISTRY.snapshot()
    assert snap.get("io/host_shard_parts") == 1
    assert snap.get("io/host_shard_index") == 0


def test_dist_runtime_env_detection(monkeypatch):
    from mxnet_tpu import dist_runtime
    for v in ("MXNET_DIST_COORDINATOR", "SLURM_JOB_ID",
              "OMPI_COMM_WORLD_SIZE", "TPU_WORKER_HOSTNAMES",
              "MEGASCALE_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS"):
        monkeypatch.delenv(v, raising=False)
    assert not dist_runtime.env_configured()
    monkeypatch.setenv("MXNET_DIST_COORDINATOR", "127.0.0.1:1234")
    assert dist_runtime.env_configured()
    monkeypatch.delenv("MXNET_DIST_COORDINATOR")
    monkeypatch.setenv("SLURM_JOB_ID", "17")
    assert dist_runtime.env_configured()
    # already-initialized runtimes are adopted, never re-initialized
    # (single-process here, so nothing is live and nothing starts)
    assert not dist_runtime.is_initialized()


def test_initialize_kvstore_pulls_broadcast_single_worker():
    """The rank-0-broadcast pull path is a no-op contract at world size
    1: init + (no) pull leaves params exactly as initialized."""
    kv = mx.kvstore.create("dist_tpu_sync")
    try:
        params = {"w": mx.nd.array(np.ones((3, 2), np.float32))}
        arrs = [mx.nd.zeros((3, 2))]
        _initialize_kvstore(kv, arrs, params, ["w"],
                            update_on_kvstore=False)
        # world size 1: no broadcast pull — local semantics preserved
        np.testing.assert_array_equal(arrs[0].asnumpy(), 0.0)
    finally:
        kv.close()


def test_host_local_value_identity_on_local_arrays():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.mesh import host_local_value
    x = jnp.arange(6.0).reshape(2, 3)
    assert host_local_value(x) is x
    assert host_local_value(np.ones(3)) is not None


# ---------------------------------------------------------------------------
# 2-process gloo acceptance
# ---------------------------------------------------------------------------

_WORKER = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, %(repo)r)
sys.path.insert(0, %(testdir)r)
rank = int(sys.argv[1])
out_path = sys.argv[2]
os.environ["MXNET_DIST_COORDINATOR"] = os.environ["COORD"]
os.environ["MXNET_DIST_NUM_PROCESSES"] = "2"
os.environ["MXNET_DIST_PROCESS_ID"] = str(rank)

import mxnet_tpu as mx
from mxnet_tpu import dist_runtime
from mxnet_tpu import io as mio
from mxnet_tpu import telemetry as tm
from mxnet_tpu.module import Module
import test_dist_tpu_sync as probe

dist_runtime.acquire()          # explicit MXNET_DIST_* route
assert jax.process_count() == 2, jax.process_count()

num_parts, part_index = mio.dist_parts()
assert (num_parts, part_index) == (2, rank)

X, Y = probe._probe_data()
it = mio.NDArrayIter(X, Y, batch_size=probe.LOCAL_BATCH, shuffle=False,
                     num_parts=num_parts, part_index=part_index)
mod = Module(probe._mlp_sym(), context=mx.cpu())
mod.bind(data_shapes=[("data", (probe.LOCAL_BATCH, probe.DIM))],
         label_shapes=[("softmax_label", (probe.LOCAL_BATCH,))])
args = probe._probe_params(mod)   # deterministic init (no RNG races)

snaps = []
def on_batch(param):
    snaps.append(tm.snapshot())

params = probe._fit(mod, it, "dist_tpu_sync", args, batch_cb=on_batch)
assert mod._kvstore is not None and mod._kvstore.type == "dist_tpu_sync"
assert mod._kvstore.num_workers == 2

steps = probe.SAMPLES // (probe.LOCAL_BATCH * 2)
snap = tm.snapshot()
reg = tm.REGISTRY.snapshot()
assert snap["fused_step_total"] == steps, snap["fused_step_total"]
assert reg.get("kvstore/allreduce_steps_total") == steps
assert reg.get("kvstore/allreduce_bytes_total", 0) > 0
assert reg.get("kvstore/dist_world_size") == 2
assert reg.get("kvstore/dist_rank") == rank
# the hot path never pushed a gradient through the kvstore: pulls
# exist only from the init-time rank-0 broadcast (one per param),
# pushes not at all — and no socket PS was ever dialed
assert "kvstore/ops_total{op=push}" not in reg
assert reg.get("kvstore/ops_total{op=pull}") == len(params)
assert reg.get("kvstore/broadcast_init_total") == len(params)
assert mod._kvstore._sock is None
# per-step telemetry: exactly ONE host dispatch per step, and zero XLA
# recompiles from step 2 on (the donated loop re-specializes once AT
# step 2 when pjit first sees its own outputs' sharding provenance)
assert len(snaps) == steps
for a, b in zip(snaps[1:], snaps[2:]):
    assert b["op_dispatch_total"] - a["op_dispatch_total"] == 1, \
        (a["op_dispatch_total"], b["op_dispatch_total"])
    assert b["backend_compile_total"] == a["backend_compile_total"], \
        "recompile after step 2"

np.savez(out_path, **params)
mod._kvstore.close()
dist_runtime.release()          # owner: clean jax.distributed shutdown
print("RANK%%d_OK" %% rank, flush=True)
""" % {"repo": REPO, "testdir": os.path.dirname(os.path.abspath(__file__))}


def test_two_process_fit_bitwise_matches_single_process(tmp_path):
    """ACCEPTANCE: fit(kvstore='dist_tpu_sync') across 2 gloo processes
    (per-host sharded input, in-program psum, one donated program per
    step) produces final params bitwise-identical across ranks AND to
    single-process training over the same global batches on a 2-device
    local dp mesh — with 1 dispatch/step and 0 recompiles after step 2
    telemetry-asserted inside each worker."""
    if not _cpu_collectives_available():
        pytest.skip(
            "this jax has no jax_cpu_collectives_implementation config: "
            "no gloo route for multiprocess CPU computations")
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    coord = "127.0.0.1:%d" % port.getsockname()[1]
    port.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu", COORD=coord,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               MXNET_FUSED_STEP="1")
    env.pop("MXNET_TPU_PS_URI", None)
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    outs = [str(tmp_path / ("params_r%d.npz" % r)) for r in range(2)]
    procs = [subprocess.Popen([sys.executable, script, str(r), outs[r]],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in range(2)]
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        logs.append(out)
    for r, (p, out) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, "rank %d:\n%s" % (r, out[-3000:])
        assert ("RANK%d_OK" % r) in out

    got = [dict(np.load(o)) for o in outs]
    assert set(got[0]) == set(got[1])
    for name in got[0]:
        assert got[0][name].tobytes() == got[1][name].tobytes(), \
            "param %r differs across ranks" % name

    # single-process twin over the SAME global batch stream: step k of
    # the 2-process run consumed [shard0 rows, shard1 rows] — feed the
    # twin exactly that concatenation on a 2-device local dp mesh (the
    # identical GSPMD partitioning; only the links differ)
    X, Y = _probe_data()
    (lo0, hi0), (lo1, hi1) = (mio.shard_bounds(SAMPLES, 2, r)
                              for r in range(2))
    xs, ys = [], []
    for k in range(SAMPLES // (LOCAL_BATCH * 2)):
        s = slice(k * LOCAL_BATCH, (k + 1) * LOCAL_BATCH)
        xs += [X[lo0:hi0][s], X[lo1:hi1][s]]
        ys += [Y[lo0:hi0][s], Y[lo1:hi1][s]]
    X_twin, Y_twin = np.concatenate(xs), np.concatenate(ys)
    mod = Module(_mlp_sym(), context=[mx.cpu(0), mx.cpu(1)])
    gb = LOCAL_BATCH * 2
    mod.bind(data_shapes=[("data", (gb, DIM))],
             label_shapes=[("softmax_label", (gb,))])
    args = _probe_params(mod)      # deterministic init shared with workers
    it = mio.NDArrayIter(X_twin, Y_twin, batch_size=gb, shuffle=False)
    twin = _fit(mod, it, "local", args)

    assert set(twin) == set(got[0])
    for name in twin:
        assert twin[name].tobytes() == got[0][name].tobytes(), \
            "param %r: dist vs single-process diverged (max |d|=%g)" % (
                name, np.max(np.abs(twin[name] - got[0][name])))
