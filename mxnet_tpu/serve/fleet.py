"""Fleet lifecycle: replica subprocesses + SLO-driven autoscaling.

:class:`Fleet` owns N replica subprocesses — each one a
``python -m mxnet_tpu.serve.fleet --worker`` running a full
:func:`~mxnet_tpu.serve.http.serve_http` stack on its own port — and
keeps a :class:`~mxnet_tpu.serve.router.Router` in sync with who is
alive and routable. Three responsibilities, one control loop:

* **replica lifecycle** — spawn (write a spec, launch the worker, wait
  for its ready-file + ``/healthz``; warm spawns ride the
  ``programs.prewarm`` warm-set manifest so a mid-ramp replica
  compiles nothing), retire (router quiesce → outstanding drains to
  zero → SIGTERM → the worker closes cleanly: zero in-flight lost),
  and per-replica stdout/stderr + flight-recorder files for
  post-mortems.
* **death triage** — a replica that exits without being retired is
  triaged by the same :class:`~mxnet_tpu.checkpoint.ProcessSupervisor`
  policy as the training supervisor: preemption-grade exits (signal
  kills, rc 137/143) always respawn; genuine failures burn a
  consecutive-failure budget (``MXNET_SUPERVISOR_MAX_FAILURES``)
  before the fleet stops replacing them. Every death writes a
  ``replica_death`` flight event; the dead replica's own ring holds
  the killer (``fault`` record before a crash-kind exit).
* **SLO-driven autoscaling** — each tick polls every replica's
  ``/alerts?format=json`` burn state and ``serving/queue_depth``
  gauge. Sustained burn or queue growth (``MXNET_FLEET_SCALE_UP_S``)
  spawns a replica up to ``MXNET_FLEET_MAX_REPLICAS``; sustained
  slack (``MXNET_FLEET_SCALE_DOWN_S``, deliberately longer) retires
  the newest one down to ``MXNET_FLEET_MIN_REPLICAS``; a cooldown
  (``MXNET_FLEET_COOLDOWN_S``) separates consecutive decisions.
  Asymmetric hold windows + cooldown are the flap hysteresis. Scale
  decisions write ``scale_up`` / ``scale_down`` flight events and move
  the ``fleet/replicas`` gauge.

The **worker** half of this module (``--worker``) builds its serving
target from the spec's ``builder`` (a ``"module:function"`` dotted
path called with the spec dict; returns the serve_http target, or a
``(target, decode)`` pair), starts ``serve_http`` on port 0, writes
``{"port", "pid"}`` to the ready-file, and parks in a ~10 Hz loop
whose every tick passes the ``fleet.replica`` fault point — the hook
chaos tests use to SIGKILL a live replica mid-traffic. SIGTERM ends
the loop and closes the frontend cleanly (exit 0 = retirement, never
triaged as a death).
"""
from __future__ import annotations

import http.client
import importlib
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

from ..base import MXNetError
from ..config import get as _cfg
from .. import blackbox as _bb
from .. import fault as _fault
from .. import programs as _pg
from .. import telemetry as _tm
from ..checkpoint import ProcessSupervisor
from .router import Router

__all__ = ["Fleet", "main"]

_monotonic = time.perf_counter

# the /alerts rules whose firing means "this replica is drowning in
# serve load" — training-side rules (badput_fraction, numerics) and
# meta-rules must not scale the fleet
BURN_RULES = frozenset(("serve_p99", "decode_itl_p99", "queue_depth"))

_QUEUE_DEPTH_RE = re.compile(
    r"^mxnet_serving_queue_depth(?:\{[^}]*\})?\s+([0-9.eE+-]+)\s*$",
    re.MULTILINE)


def _http_get(host, port, path, timeout=2.0):
    """(status, body bytes) of one GET, or (None, b"") on any
    connection-level failure — the poller treats those as 'replica not
    answering', never as fatal."""
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()
    except (OSError, http.client.HTTPException):
        return None, b""


class _Replica(object):
    """Parent-side record of one replica subprocess."""

    __slots__ = ("name", "proc", "port", "spawned_t", "ready_t",
                 "retiring", "warm", "logfile")

    def __init__(self, name, proc, logfile):
        self.name = name
        self.proc = proc
        self.port = None
        self.spawned_t = _monotonic()
        self.ready_t = None
        self.retiring = False
        self.warm = False
        self.logfile = logfile


class Fleet(object):
    """Spawn, scale, retire, and triage ``serve_http`` replicas behind
    a :class:`~mxnet_tpu.serve.router.Router`.

    ``spec``: a JSON-serializable dict with at least ``builder``
    ("module:function" building the worker's serving target from the
    spec); optional ``pythonpath`` (list, prepended to the worker's
    ``sys.path``) and ``env`` (dict folded into the worker
    environment). ``signals_fn`` (tests): replaces the HTTP signal
    poll with a callable returning
    ``[{"name", "firing": [...], "queue_depth": float|None}, ...]``.
    """

    def __init__(self, spec, workdir, router=None, min_replicas=None,
                 max_replicas=None, interval_s=None, scale_up_s=None,
                 scale_down_s=None, cooldown_s=None, queue_up=None,
                 queue_down=None, spawn_timeout_s=None,
                 drain_timeout_s=None, signals_fn=None, env=None,
                 python=None):
        def pick(v, name):
            return _cfg(name) if v is None else v
        self.spec = dict(spec)
        if "builder" not in self.spec:
            raise MXNetError('fleet spec needs a "builder" '
                             '("module:function")')
        self.workdir = os.path.abspath(os.fspath(workdir))
        os.makedirs(self.workdir, exist_ok=True)
        self.router = router if router is not None else Router()
        self.min_replicas = int(pick(min_replicas,
                                     "MXNET_FLEET_MIN_REPLICAS"))
        self.max_replicas = int(pick(max_replicas,
                                     "MXNET_FLEET_MAX_REPLICAS"))
        self.interval_s = float(pick(interval_s,
                                     "MXNET_FLEET_INTERVAL_S"))
        self.scale_up_s = float(pick(scale_up_s,
                                     "MXNET_FLEET_SCALE_UP_S"))
        self.scale_down_s = float(pick(scale_down_s,
                                       "MXNET_FLEET_SCALE_DOWN_S"))
        self.cooldown_s = float(pick(cooldown_s,
                                     "MXNET_FLEET_COOLDOWN_S"))
        self.queue_up = float(pick(queue_up, "MXNET_FLEET_QUEUE_UP"))
        self.queue_down = float(pick(queue_down,
                                     "MXNET_FLEET_QUEUE_DOWN"))
        self.spawn_timeout_s = float(pick(spawn_timeout_s,
                                          "MXNET_FLEET_SPAWN_TIMEOUT_S"))
        self.drain_timeout_s = float(pick(drain_timeout_s,
                                          "MXNET_FLEET_DRAIN_TIMEOUT_S"))
        if not (1 <= self.min_replicas <= self.max_replicas):
            raise MXNetError("need 1 <= min_replicas <= max_replicas "
                             "(got %d..%d)" % (self.min_replicas,
                                               self.max_replicas))
        self.signals_fn = signals_fn
        self.base_env = dict(env or {})
        self.python = python or sys.executable
        self.supervisor = ProcessSupervisor(relaunch_delay_s=0.0)
        self.target = self.min_replicas
        self._replicas = {}              # name -> _Replica
        self._counter = 0
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread = None
        self._hot_since = None
        self._cold_since = None
        self._last_scale = None
        self._degraded = None            # failure-budget exhaustion note
        self._spec_path = os.path.join(self.workdir, "spec.json")
        with open(self._spec_path, "w") as f:
            json.dump(self.spec, f)
        self.router.set_fleet_status_fn(self.status)

    # -- spawning --------------------------------------------------------

    def _next_name(self):
        self._counter += 1
        return "r%d" % self._counter

    def _warm_manifest_present(self, env):
        # env is the child's full environment; without a placement
        # from outside the child resolves the same default as we do
        cache = env.get("JAX_COMPILATION_CACHE_DIR") or _pg.cache_dir()
        return os.path.exists(_pg.warmset_path(cache))

    def _spawn(self, reason):
        """Launch one worker and wait for it to serve; registers it
        with the router on success. Returns the replica name, or None
        when the spawn failed (triaged like a death)."""
        with self._lock:
            name = self._next_name()
        ready = os.path.join(self.workdir, name + ".ready.json")
        try:
            os.unlink(ready)
        except OSError:
            pass
        env = dict(os.environ)
        env.update(self.base_env)
        env.update({str(k): str(v)
                    for k, v in (self.spec.get("env") or {}).items()})
        # the worker must run the same mxnet_tpu tree as this parent
        # (which may be an uninstalled source checkout): prepend it
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        pp = env.get("PYTHONPATH", "")
        if pkg_root not in pp.split(os.pathsep):
            env["PYTHONPATH"] = pkg_root + (os.pathsep + pp
                                            if pp else "")
        # each replica gets its own flight ring next to the parent's:
        # concurrent appenders on one ring would interleave rotation
        if _bb.enabled() and "MXNET_FLIGHT_RECORDER" not in \
                (self.spec.get("env") or {}):
            env["MXNET_FLIGHT_RECORDER"] = os.path.join(
                os.path.dirname(os.path.abspath(_bb.path())),
                "flight-%s.bin" % name)
        logfile = open(os.path.join(self.workdir, name + ".log"), "ab")
        proc = subprocess.Popen(
            [self.python, "-m", "mxnet_tpu.serve.fleet", "--worker",
             "--spec", self._spec_path, "--ready-file", ready,
             "--name", name],
            stdout=logfile, stderr=subprocess.STDOUT, env=env,
            cwd=self.workdir)
        rep = _Replica(name, proc, logfile)
        rep.warm = self._warm_manifest_present(env)
        with self._lock:
            self._replicas[name] = rep
        if not self._wait_ready(rep):
            return None
        self.router.add(name, "127.0.0.1", rep.port)
        self.supervisor.note_success()
        live = self.live_count()
        _bb.record_event("scale_up", replica=name, reason=reason,
                         live=live, warm=rep.warm)
        if _tm._enabled:
            _tm.gauge("fleet/replicas",
                      "Live (ready + routable) fleet replicas"
                      ).set(live)
            _tm.histogram("fleet/spawn_seconds",
                          "Replica spawn-to-ready latency",
                          ("warm",)).labels(
                              "1" if rep.warm else "0").observe(
                              rep.ready_t - rep.spawned_t)
        return name

    def _wait_ready(self, rep):
        """Ready-file then /healthz, bounded by ``spawn_timeout_s``.
        A death or timeout during the wait is triaged + cleaned up."""
        ready = os.path.join(self.workdir, rep.name + ".ready.json")
        deadline = _monotonic() + self.spawn_timeout_s
        while _monotonic() < deadline:
            rc = rep.proc.poll()
            if rc is not None:
                self._note_death(rep, rc, during="spawn")
                return False
            if rep.port is None:
                try:
                    with open(ready) as f:
                        rep.port = int(json.load(f)["port"])
                except (OSError, ValueError, KeyError):
                    time.sleep(0.02)
                    continue
            status, body = _http_get("127.0.0.1", rep.port, "/healthz",
                                     timeout=1.0)
            if status == 200 and body.strip() == b"ok":
                rep.ready_t = _monotonic()
                return True
            time.sleep(0.02)
        # timed out: kill it and triage as a failure
        try:
            rep.proc.kill()
            rep.proc.wait(timeout=5)
        except OSError:
            pass
        self._note_death(rep, rep.proc.poll() or 1, during="spawn")
        return False

    # -- retirement ------------------------------------------------------

    def _retire(self, name, reason):
        """Drain-then-stop: router quiesce (no new picks), wait for
        outstanding to hit zero, SIGTERM, reap. Zero in-flight lost —
        the replica only dies after the router saw its last response
        out."""
        with self._lock:
            rep = self._replicas.get(name)
            if rep is None or rep.retiring:
                return False
            rep.retiring = True
        self.router.quiesce(name)
        deadline = _monotonic() + self.drain_timeout_s
        while self.router.outstanding(name) > 0 \
                and _monotonic() < deadline:
            time.sleep(0.02)
        try:
            rep.proc.send_signal(signal.SIGTERM)
        except OSError:
            pass
        try:
            rep.proc.wait(timeout=self.drain_timeout_s)
        except subprocess.TimeoutExpired:
            rep.proc.kill()
            rep.proc.wait(timeout=5)
        self.router.remove(name)
        self._forget(rep)
        live = self.live_count()
        _bb.record_event("scale_down", replica=name, reason=reason,
                         live=live)
        if _tm._enabled:
            _tm.gauge("fleet/replicas",
                      "Live (ready + routable) fleet replicas"
                      ).set(live)
        return True

    def _forget(self, rep):
        with self._lock:
            self._replicas.pop(rep.name, None)
        try:
            rep.logfile.close()
        except OSError:
            pass

    # -- death triage ----------------------------------------------------

    def _note_death(self, rep, rc, during="serve"):
        """An unretired replica exited: flight-record it, triage with
        the shared supervisor policy, drop it from the router."""
        self.router.remove(rep.name)
        self._forget(rep)
        reason, relaunch = self.supervisor.triage(
            rc, what="fleet replica %s" % rep.name)
        if not relaunch:
            self._degraded = ("replica %s rc %d exhausted the "
                              "failure budget" % (rep.name, rc))
        _bb.record_event("replica_death", replica=rep.name, rc=rc,
                         reason=reason, respawn=relaunch,
                         during=during, live=self.live_count())
        if _tm._enabled:
            _tm.gauge("fleet/replicas",
                      "Live (ready + routable) fleet replicas"
                      ).set(self.live_count())
        return relaunch

    def _reap(self):
        """Collect replicas that died out from under us; respawn while
        the failure budget allows (a preemption-grade SIGKILL always
        does)."""
        with self._lock:
            dead = [r for r in self._replicas.values()
                    if not r.retiring and r.proc.poll() is not None]
        for rep in dead:
            self._note_death(rep, rep.proc.poll())

    # -- signals + autoscaler --------------------------------------------

    def _poll_signals(self):
        """One row per ready replica: the firing /alerts rules (json
        format) and the serving/queue_depth gauge scraped from
        /metrics."""
        rows = []
        with self._lock:
            reps = [(r.name, r.port) for r in self._replicas.values()
                    if r.port is not None and not r.retiring]
        for name, port in reps:
            row = {"name": name, "firing": [], "queue_depth": None}
            status, body = _http_get("127.0.0.1", port,
                                     "/alerts?format=json")
            if status == 200:
                try:
                    row["firing"] = list(
                        json.loads(body.decode())["firing"])
                except (ValueError, KeyError, UnicodeDecodeError):
                    pass
            status, body = _http_get("127.0.0.1", port, "/metrics")
            if status == 200:
                m = _QUEUE_DEPTH_RE.search(body.decode("utf-8",
                                                       "replace"))
                if m:
                    row["queue_depth"] = float(m.group(1))
            rows.append(row)
        return rows

    def _autoscale(self, now=None):
        """One hysteresis step: sustained burn/queue pressure raises
        the target, sustained slack lowers it, a cooldown separates
        decisions. Returns "up"/"down"/None (what this step did)."""
        now = _monotonic() if now is None else now
        signals = (self.signals_fn() if self.signals_fn is not None
                   else self._poll_signals())
        burn = sorted({rule for s in signals
                       for rule in s.get("firing", ())
                       if rule in BURN_RULES})
        queues = [s["queue_depth"] for s in signals
                  if s.get("queue_depth") is not None]
        mean_q = sum(queues) / len(queues) if queues else 0.0
        max_q = max(queues) if queues else 0.0
        hot = bool(burn) or mean_q > self.queue_up
        cold = not burn and max_q <= self.queue_down
        self._hot_since = (self._hot_since or now) if hot else None
        self._cold_since = (self._cold_since or now) if cold else None
        in_cooldown = (self._last_scale is not None
                       and now - self._last_scale < self.cooldown_s)
        if in_cooldown:
            return None
        if hot and now - self._hot_since >= self.scale_up_s \
                and self.target < self.max_replicas:
            self.target += 1
            self._last_scale = now
            self._hot_since = None
            self._spawn("burn:%s" % ",".join(burn) if burn
                        else "queue:%.1f" % mean_q)
            return "up"
        if cold and now - self._cold_since >= self.scale_down_s \
                and self.target > self.min_replicas:
            self.target -= 1
            self._last_scale = now
            self._cold_since = None
            newest = None
            with self._lock:
                live = [r for r in self._replicas.values()
                        if not r.retiring]
                if live:
                    newest = max(live, key=lambda r: r.spawned_t).name
            if newest is not None:
                self._retire(newest, "slack")
            return "down"
        return None

    def tick(self):
        """One control-loop step: reap deaths, re-converge to target,
        autoscale. Callable directly (tests drive it synchronously)."""
        self._reap()
        while self.live_count() < self.target \
                and self._degraded is None:
            if self._spawn("respawn") is None and \
                    self._degraded is not None:
                break
        return self._autoscale()

    def live_count(self):
        with self._lock:
            return sum(1 for r in self._replicas.values()
                       if not r.retiring)

    # -- lifecycle -------------------------------------------------------

    def start(self):
        """Spawn the initial fleet and start the control loop."""
        while self.live_count() < self.target \
                and self._degraded is None:
            self._spawn("initial")
        if self._degraded is not None:
            self.close()
            raise MXNetError("fleet failed to start: %s"
                             % self._degraded)
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="mxnet-fleet", daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "fleet control tick failed")

    def status(self):
        with self._lock:
            reps = [{"name": r.name, "pid": r.proc.pid, "port": r.port,
                     "endpoint": ("127.0.0.1:%d" % r.port
                                  if r.port is not None else None),
                     "retiring": r.retiring, "warm": r.warm,
                     "spawn_s": (round(r.ready_t - r.spawned_t, 3)
                                 if r.ready_t else None)}
                    for r in self._replicas.values()]
        return {"target": self.target, "live": self.live_count(),
                "min": self.min_replicas, "max": self.max_replicas,
                "degraded": self._degraded, "replicas": reps}

    def close(self):
        """Stop the control loop and tear every replica down (SIGTERM,
        then SIGKILL stragglers)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        with self._lock:
            reps = list(self._replicas.values())
        for rep in reps:
            try:
                rep.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for rep in reps:
            try:
                rep.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                rep.proc.kill()
                rep.proc.wait(timeout=5)
            self.router.remove(rep.name)
            self._forget(rep)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# worker entry: python -m mxnet_tpu.serve.fleet --worker ...
# ---------------------------------------------------------------------------

def _load_builder(spec):
    for p in spec.get("pythonpath") or ():
        if p not in sys.path:
            sys.path.insert(0, p)
    dotted = spec["builder"]
    mod_name, _, fn_name = dotted.partition(":")
    if not fn_name:
        raise MXNetError('builder %r is not "module:function"'
                         % dotted)
    return getattr(importlib.import_module(mod_name), fn_name)


def _worker_main(args):
    with open(args.spec) as f:
        spec = json.load(f)
    built = _load_builder(spec)(spec)
    target, decode = (built if isinstance(built, tuple)
                      else (built, None))
    from .http import serve_http
    srv = serve_http(target, port=0, decode=decode)
    tmp = args.ready_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"port": srv.port, "pid": os.getpid(),
                   "name": args.name}, f)
    os.replace(tmp, args.ready_file)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    # ~10 Hz park loop; every tick passes the fleet.replica fault
    # point so an env-armed crash kind can SIGKILL this replica at a
    # deterministic tick mid-traffic
    while not stop.wait(0.1):
        _fault.inject("fleet.replica")
    srv.close()
    closer = getattr(target, "close", None)
    if callable(closer):
        closer()
    return 0


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.serve.fleet",
        description="Fleet replica worker (spawned by serve.Fleet).")
    ap.add_argument("--worker", action="store_true", required=True)
    ap.add_argument("--spec", required=True,
                    help="path to the fleet spec JSON")
    ap.add_argument("--ready-file", required=True,
                    help="written as {\"port\", \"pid\"} once serving")
    ap.add_argument("--name", default="replica")
    return _worker_main(ap.parse_args(argv))


if __name__ == "__main__":
    raise SystemExit(main())
