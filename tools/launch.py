#!/usr/bin/env python
"""Local cluster launcher for distributed KVStore jobs.

Capability analog of the reference's tools/launch.py (dmlc tracker:
spawns scheduler + servers + workers with DMLC_ROLE env, supporting
ssh/mpi/yarn/local launchers). TPU deployments get multi-host process
bootstrap from jax.distributed / the cluster scheduler, so this tool
covers the remaining case the reference's dist tests rely on: forking
a parameter server + N workers on ONE host to exercise dist kvstore
semantics end-to-end (tests/nightly/dist_sync_kvstore.py pattern).

Usage:
    python tools/launch.py -n 2 [--sync-mode sync|async] \
        python my_training_script.py --kv-store dist_async

    # multi-host over ssh (reference: dmlc-core tracker ssh.py): the
    # parameter server runs HERE; workers round-robin over --hostfile
    python tools/launch.py -n 4 --launcher ssh --hostfile hosts.txt \
        python my_training_script.py --kv-store dist_async

Env exported to children (reference: DMLC_ROLE / DMLC_PS_ROOT_URI):
    MXNET_TPU_ROLE, MXNET_TPU_PS_URI, MXNET_TPU_PS_PORT,
    MXNET_TPU_NUM_WORKERS, MXNET_TPU_RANK, MXNET_TPU_PS_MODE

The local launcher additionally exports the ``MXNET_DIST_*`` contract
(coordinator address + world size + per-worker process id) so a script
running ``--kv-store dist_tpu_sync`` rendezvouses a ``jax.distributed``
runtime and trains over in-program collectives — the kvstore type the
script picks decides which transport it actually dials; the PS is
started either way and simply idles for collective-only jobs. Multi-host
ssh deployments get the runtime from the cluster scheduler's standard
env instead (see docs/distributed_training.md).
"""
import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _local_uri():
    """A routable address for remote workers to reach the PS."""
    try:
        # a UDP connect picks the outbound interface without sending
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        uri = s.getsockname()[0]
        s.close()
        return uri
    except OSError:
        return socket.gethostbyname(socket.gethostname())


def _ssh_worker_cmd(host, ssh_port, env, command, cwd):
    """Build the ssh invocation for one remote worker: environment is
    passed inline (sshd's AcceptEnv rarely covers custom vars)."""
    exports = " ".join("%s=%s" % (k, shlex.quote(str(v)))
                       for k, v in sorted(env.items()))
    remote = "cd %s && env %s %s" % (
        shlex.quote(cwd), exports,
        " ".join(shlex.quote(c) for c in command))
    return ["ssh", "-p", str(ssh_port), "-o", "StrictHostKeyChecking=no",
            "-o", "BatchMode=yes", host, remote]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh"])
    ap.add_argument("--hostfile",
                    help="ssh launcher: file with one host per line")
    ap.add_argument("--ssh-port", type=int, default=22)
    ap.add_argument("--ps-uri", default=None,
                    help="address workers use to reach the PS "
                         "(default: auto-detect; 127.0.0.1 for local)")
    ap.add_argument("--sync-mode", default="sync",
                    choices=["sync", "async"])
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for children")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        ap.error("no command given")

    hosts = None
    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh requires --hostfile")
        with open(args.hostfile) as f:
            hosts = [ln.strip() for ln in f if ln.strip()
                     and not ln.startswith("#")]
        if not hosts:
            ap.error("hostfile %s has no hosts" % args.hostfile)

    port = _free_port()
    ps_uri = args.ps_uri or ("127.0.0.1" if args.launcher == "local"
                             else _local_uri())
    base_env = dict(os.environ)
    for kv in args.env:
        k, _, v = kv.partition("=")
        base_env[k] = v
    import uuid
    base_env.update({
        "MXNET_TPU_PS_URI": ps_uri,
        "MXNET_TPU_PS_PORT": str(port),
        "MXNET_TPU_NUM_WORKERS": str(args.num_workers),
        "MXNET_TPU_PS_MODE": args.sync_mode,
        # shared secret for the pickle wire protocol (server rejects
        # unauthenticated peers)
        "MXNET_TPU_PS_TOKEN": uuid.uuid4().hex,
    })
    if args.launcher == "local":
        # dist_tpu_sync route: rank 0 hosts the jax.distributed
        # coordinator on its own port (the PS port carries pickle
        # RPCs, not gRPC)
        base_env.update({
            "MXNET_DIST_COORDINATOR": "127.0.0.1:%d" % _free_port(),
            "MXNET_DIST_NUM_PROCESSES": str(args.num_workers),
        })

    # the parameter server is a host-side service: pinned to the CPU
    # platform so it can never claim the chips its workers need
    server_env = dict(base_env, MXNET_TPU_ROLE="server",
                      JAX_PLATFORMS="cpu")
    server = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.kvstore_server"], env=server_env)
    # wait until the listener actually accepts (a fixed sleep flakes on
    # loaded hosts where interpreter startup alone can take seconds)
    deadline = time.time() + 120.0
    while True:
        if server.poll() is not None:
            sys.exit("kvstore server exited rc=%d before binding"
                     % server.returncode)
        try:
            probe = socket.create_connection(("127.0.0.1", port),
                                             timeout=1.0)
            probe.close()
            break
        except OSError:
            if time.time() > deadline:
                server.kill()
                sys.exit("kvstore server failed to bind within 120s")
            time.sleep(0.2)

    # everything after the server exists runs under try/finally: an
    # orphaned server would inherit the caller's stdout/stderr pipes and
    # hang a capturing parent long after launch.py itself exits
    rc = 0
    workers = []
    try:
        for rank in range(args.num_workers):
            wenv = dict(base_env, MXNET_TPU_ROLE="worker",
                        MXNET_TPU_RANK=str(rank),
                        MXNET_DIST_PROCESS_ID=str(rank))
            if hosts is not None:
                # the remote side gets ONLY the contract env inline;
                # its login shell provides the rest
                contract = {k: wenv[k] for k in wenv
                            if k.startswith("MXNET_TPU_")}
                cmd = _ssh_worker_cmd(hosts[rank % len(hosts)],
                                      args.ssh_port, contract,
                                      args.command, os.getcwd())
                workers.append(subprocess.Popen(cmd))
            else:
                workers.append(subprocess.Popen(args.command, env=wenv))
        for w in workers:
            rc |= w.wait()
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
        server.send_signal(signal.SIGTERM)
        try:
            server.wait(timeout=5)
        except subprocess.TimeoutExpired:
            server.kill()
    sys.exit(rc)


if __name__ == "__main__":
    main()
