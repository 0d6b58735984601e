"""Distributed KVStore: PS server, gradient compression, launcher.

Mirrors the reference's dist tests (tests/nightly/dist_sync_kvstore.py:
consistency of dense/compressed push-pull across ranks, launched via
tools/launch.py --launcher local) scaled down for CI.
"""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gradient_compression import TwoBitCompressor, Int8Compressor
from mxnet_tpu.kvstore_server import KVStoreServer


# ---------------------------------------------------------------------------
# compression codecs
# ---------------------------------------------------------------------------

def test_2bit_quantization_values():
    c = TwoBitCompressor(threshold=0.5)
    x = np.array([0.7, -0.9, 0.1, -0.2, 0.5, 0.49], np.float32)
    y = c.roundtrip("k", x)
    np.testing.assert_allclose(y, [0.5, -0.5, 0, 0, 0.5, 0], atol=0)


def test_2bit_error_feedback_accumulates():
    c = TwoBitCompressor(threshold=0.5)
    x = np.full((8,), 0.3, np.float32)
    y1 = c.roundtrip("k", x)          # 0.3 < t -> 0, residual 0.3
    y2 = c.roundtrip("k", x)          # 0.6 >= t -> +t
    assert np.all(y1 == 0.0)
    assert np.all(y2 == 0.5)
    # long-run mean approaches the true value (unbiased-ish via feedback)
    total = y1 + y2
    for _ in range(18):
        total += c.roundtrip("k", x)
    assert abs(total.mean() / 20 - 0.3) < 0.05


def test_2bit_packing_density():
    c = TwoBitCompressor(threshold=1.0)
    x = np.random.RandomState(0).randn(1024).astype(np.float32)
    packed, shape = c.compress("k", x)
    assert packed.nbytes == 1024 // 4          # 2 bits per value
    assert c.decompress(packed, shape).shape == (1024,)


def test_int8_compressor_close():
    c = Int8Compressor()
    x = np.random.RandomState(1).randn(256).astype(np.float32)
    y = c.roundtrip("k", x)
    assert np.max(np.abs(x - y)) < np.max(np.abs(x)) / 100


def test_kvstore_local_compression_applies():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((4,)))
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    out = mx.nd.zeros((4,))
    kv.push("w", mx.nd.array(np.array([0.7, 0.1, -0.9, 0.0], np.float32)))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, 0, -0.5, 0])


# ---------------------------------------------------------------------------
# PS server (threads in-process)
# ---------------------------------------------------------------------------

def _worker(port, rank, nw, results, mode="sync"):
    env = {"MXNET_TPU_PS_URI": "127.0.0.1", "MXNET_TPU_PS_PORT": str(port),
           "MXNET_TPU_RANK": str(rank), "MXNET_TPU_NUM_WORKERS": str(nw)}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        # dist_sync: the socket-PS BSP tier. (dist_tpu_sync no longer
        # dials the PS at all — its sync hot path is the in-program
        # collective; see tests/test_dist_tpu_sync.py)
        kv = mx.kv.create("dist_async" if mode == "async" else
                          "dist_sync")
        kv.init("w", mx.nd.zeros((4,)))
        kv.barrier()
        kv.push("w", mx.nd.array(
            np.full((4,), float(rank + 1), np.float32)))
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        results[rank] = out.asnumpy()
        kv.barrier()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_ps_sync_aggregate_then_update():
    server = KVStoreServer(port=0, num_workers=2, sync_mode=True)
    server.start_background()
    results = {}
    ts = [threading.Thread(target=_worker,
                           args=(server.port, r, 2, results))
          for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    server.stop()
    # no optimizer on server -> store holds the aggregated sum 1+2=3
    np.testing.assert_allclose(results[0], np.full((4,), 3.0))
    np.testing.assert_allclose(results[1], np.full((4,), 3.0))


def test_ps_async_immediate_update():
    server = KVStoreServer(port=0, num_workers=1, sync_mode=False)
    server.start_background()
    results = {}
    _worker(server.port, 0, 1, results, mode="async")
    server.stop()
    np.testing.assert_allclose(results[0], np.full((4,), 1.0))


def test_ps_server_side_optimizer():
    import pickle
    from mxnet_tpu.kvstore_server import send_msg, recv_msg
    import socket
    server = KVStoreServer(port=0, num_workers=1, sync_mode=True)
    server.start_background()
    s = socket.socket()
    s.connect(("127.0.0.1", server.port))

    def call(op, key=None, value=None):
        send_msg(s, (op, key, value))
        return recv_msg(s)

    opt = mx.optimizer.SGD(learning_rate=0.5)
    assert call("SET_OPTIMIZER", None, pickle.dumps(opt))[0] == "OK"
    assert call("INIT", "w", np.ones((3,), np.float32))[0] == "OK"
    assert call("PUSH", "w", np.full((3,), 2.0, np.float32))[0] == "OK"
    st, w = call("PULL", "w")[:2]
    server.stop()
    # w = 1 - 0.5 * 2 = 0 (sgd on the server, ApplyUpdates analog)
    np.testing.assert_allclose(w, np.zeros((3,)), atol=1e-6)


def test_ps_row_sparse_pull():
    from mxnet_tpu.kvstore_server import send_msg, recv_msg
    import socket
    server = KVStoreServer(port=0, num_workers=1, sync_mode=True)
    server.start_background()
    s = socket.socket()
    s.connect(("127.0.0.1", server.port))
    send_msg(s, ("INIT", "emb", np.arange(12, dtype=np.float32).reshape(4, 3)))
    recv_msg(s)
    send_msg(s, ("PULL_ROWS", "emb", np.array([2, 0], np.int64)))
    st, sub = recv_msg(s)[:2]
    server.stop()
    np.testing.assert_allclose(sub, [[6, 7, 8], [0, 1, 2]])


def test_ps_compressed_push():
    from mxnet_tpu.kvstore_server import send_msg, recv_msg
    from mxnet_tpu.gradient_compression import TwoBitCompressor
    import socket
    server = KVStoreServer(port=0, num_workers=1, sync_mode=True)
    server.start_background()
    s = socket.socket()
    s.connect(("127.0.0.1", server.port))
    send_msg(s, ("SET_COMPRESSION", None, {"type": "2bit",
                                           "threshold": 0.5}))
    recv_msg(s)
    send_msg(s, ("INIT", "w", np.zeros((4,), np.float32)))
    recv_msg(s)
    c = TwoBitCompressor(threshold=0.5)
    payload = c.compress("w", np.array([0.7, 0.1, -0.9, 0.0], np.float32))
    send_msg(s, ("PUSH", "w", payload))
    st, err = recv_msg(s)[:2]
    assert st == "OK", err
    send_msg(s, ("PULL", "w"))
    st, w = recv_msg(s)[:2]
    server.stop()
    assert st == "OK", w
    np.testing.assert_allclose(w, [0.5, 0, -0.5, 0])


# ---------------------------------------------------------------------------
# launcher end-to-end (real processes)
# ---------------------------------------------------------------------------

_WORKER_SCRIPT = r"""
import os
import numpy as np
import mxnet_tpu as mx
rank = int(os.environ["MXNET_TPU_RANK"])
kv = mx.kv.create("dist_sync")
kv.init("x", mx.nd.zeros((2,)))
kv.barrier()
kv.push("x", mx.nd.array(np.full((2,), float(rank + 1), np.float32)))
out = mx.nd.zeros((2,))
kv.pull("x", out=out)
assert np.allclose(out.asnumpy(), 3.0), out.asnumpy()
print("worker %d ok" % rank)
"""


@pytest.mark.slow
def test_launch_local_two_workers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "worker 0 ok" in proc.stdout
    assert "worker 1 ok" in proc.stdout


def test_server_profiler_remote_control(tmp_path):
    """Remote profiler start/config/dump on the PS server PROCESS
    (reference: KVStoreServerProfilerCommand, include/mxnet/kvstore.h:49;
    tests/nightly/test_server_profiling.py): the worker drives
    profiler.set_config/set_state/dump with profile_process='server'
    and the trace file appears, written by the server subprocess."""
    import json
    import time

    profile_path = str(tmp_path / "server_profile.json")
    port_file = str(tmp_path / "port.txt")
    code = (
        "import sys\n"
        "from mxnet_tpu.kvstore_server import KVStoreServer\n"
        "s = KVStoreServer(port=0, num_workers=1, sync_mode=True)\n"
        "open(%r, 'w').write(str(s.port))\n"
        "s.serve_forever()\n" % port_file
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            cwd=os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))))
    try:
        for _ in range(100):
            if os.path.exists(port_file) and open(port_file).read():
                break
            time.sleep(0.2)
        port = int(open(port_file).read())

        envvars = {"MXNET_TPU_PS_URI": "127.0.0.1",
                   "MXNET_TPU_PS_PORT": str(port),
                   "MXNET_TPU_RANK": "0", "MXNET_TPU_NUM_WORKERS": "1"}
        old = {k: os.environ.get(k) for k in envvars}
        os.environ.update(envvars)
        try:
            from mxnet_tpu import profiler
            kv = mx.kv.create("dist_sync")
            profiler.set_kvstore_handle(kv)
            profiler.set_config(filename=profile_path, profile_all=True,
                                profile_process="server")
            profiler.set_state("run", profile_process="server")
            kv.init("w", mx.nd.zeros((4,)))
            kv.push("w", mx.nd.ones((4,)))
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)
            profiler.set_state("stop", profile_process="server")
            profiler.dump(profile_process="server")
            kv._ps_call("STOP")
        finally:
            profiler.set_kvstore_handle(None)
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    finally:
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()

    assert os.path.exists(profile_path)
    with open(profile_path) as f:
        trace = json.load(f)
    names = {e.get("name") for e in trace["traceEvents"]}
    assert any(n and n.startswith("kvstore_") for n in names), names
    # events carry the SERVER process pid, not the worker's
    pids = {e.get("pid") for e in trace["traceEvents"]}
    assert os.getpid() not in pids


def test_launch_ssh_two_workers(tmp_path):
    """--launcher ssh builds per-host ssh invocations carrying the PS
    contract env; proven end to end with a stub `ssh` that executes the
    remote command locally (the dmlc tracker ssh.py pattern)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    ssh = fake_bin / "ssh"
    # drop option pairs + host, run the remote command string locally
    ssh.write_text(
        "#!/bin/sh\n"
        "while [ $# -gt 1 ]; do\n"
        "  case \"$1\" in -p|-o) shift 2;; *) break;; esac\n"
        "done\n"
        "host=\"$1\"; shift\n"
        "echo \"fake-ssh to $host\" >&2\n"
        "exec /bin/sh -c \"$*\"\n")
    ssh.chmod(0o755)

    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "import numpy as np\n"
        "kv = mx.kv.create('dist_async')\n"
        "kv.init('w', mx.nd.zeros((3,)))\n"
        "kv.push('w', mx.nd.ones((3,)))\n"
        "out = mx.nd.zeros((3,))\n"
        "kv.pull('w', out=out)\n"
        "print('RANK', kv.rank, 'SUM', float(out.asnumpy().sum()))\n"
        % repo)
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("nodeA\nnodeB\n")

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PATH=str(fake_bin) + os.pathsep + os.environ["PATH"])
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--launcher", "ssh", "--hostfile", str(hostfile),
         "--sync-mode", "async", "--ps-uri", "127.0.0.1",
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fake-ssh to nodeA" in r.stderr and \
        "fake-ssh to nodeB" in r.stderr, r.stderr
    # two workers completed (lines may interleave on a shared pipe)
    assert r.stdout.count("SUM 3.0") == 2, r.stdout


def test_kill_mxnet_tool(tmp_path):
    """tools/kill_mxnet.py (reference kill-mxnet.py analog) finds and
    terminates a stray PS server without touching itself."""
    import time
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import time\n"
            "from mxnet_tpu.kvstore_server import KVStoreServer\n"
            "s = KVStoreServer(port=0, num_workers=1)\n"
            "s.start_background()\n"
            "time.sleep(120)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            cwd=repo)
    try:
        time.sleep(2)
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "kill_mxnet.py"),
             "--pattern", "kvstore_server"],
            capture_output=True, text=True, timeout=60, cwd=repo)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "killing pid %d" % proc.pid in r.stdout, r.stdout
        proc.wait(timeout=15)
        assert proc.returncode is not None
    finally:
        if proc.poll() is None:
            proc.kill()
