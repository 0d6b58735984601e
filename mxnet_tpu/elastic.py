"""Elastic membership control plane for ``dist_tpu_sync``.

PR 7 gave the *socket* tiers elastic membership (heartbeats, death
detection, membership epochs, rejoin) living inside the parameter
server.  The collectives tier has no server to put that state in —
every rank is a peer inside one donated XLA program — so this module
is the replacement: a lightweight DCN-side control plane that lives
BESIDE the data plane and never touches the hot step path.

Transport: files in a shared directory (``MXNET_ELASTIC_DIR``) written
atomically (tmp + rename) and polled.  On a TPU pod every host mounts
the same staging volume (the PR 14 compile cache already relies on
one); on one machine (the CPU/gloo chaos tests) it is just a tmpdir.
A socket transport can slot in behind the same ``ElasticAgent``
surface later — the protocol below is deliberately transport-dumb.

Protocol (all JSON, one file per fact, ``gen`` = membership epoch):

* ``cluster.json`` — written once by the initial rank 0:
  ``{"base_world": B}``.  B never changes; it is the number of dataset
  parts and the unit of gradient microbatching (a W-survivor world
  runs B/W microbatches per step so the global batch — and the loss
  curve — is invariant across rescales).
* ``hb-g<gen>-r<rank>.json`` — per-member heartbeat, rewritten every
  ``MXNET_ELASTIC_HB_S``: rank, pid, advertised host, last completed
  step.  A member whose heartbeat is older than ``MXNET_DIST_DEAD_S``
  is lost.
* ``vote-g<gen>-r<rank>.json`` — a survivor's rescale-barrier vote:
  the last step it completed globally.
* ``plan-g<gen>.json`` — THE rescale decision, written exactly once
  per generation by the rescale coordinator (the lowest-ranked live
  survivor): the new membership (old rank -> new rank, joiners
  appended), new world size, fresh coordinator address, agreed resume
  step (min over votes), grad-accum factor per member.
* ``join-<nonce>.json`` — a joiner's request (rewritten as its
  heartbeat until admitted).  Survivors admit joiners at the next
  step boundary by running the same barrier with ``grow=True``.

Agreement argument: votes carry the last *completed* step.  Under BSP
every rank participates in every all-reduce, so when a rank dies
mid-step no survivor can have completed that step — survivor votes
differ by at most the one step that was in flight, and ``min`` picks
the last *globally* completed one.  Joiners have no vote.

Clocks: liveness compares a reader's ``time.time()`` with the writer's
embedded timestamp — hosts sharing the control-plane volume are
assumed NTP-sane within a fraction of ``MXNET_DIST_DEAD_S`` (the same
assumption the PR 7 socket heartbeats make about RTT).
"""
from __future__ import annotations

import json
import logging
import os
import socket
import threading
import time

from .base import MXNetError

__all__ = ["ElasticAgent", "ElasticFit", "MembershipChange",
           "StepStallError", "call_bounded", "free_port",
           "plan_microbatches", "rescale_errors"]

_log = logging.getLogger(__name__)


def _cfg(name):
    from .config import get
    return get(name)


def _tm():
    from . import telemetry
    return telemetry


def _telemetry_endpoint():
    """``"host:port"`` of this process's running metrics server, or
    None — what the heartbeat publishes for observatory discovery."""
    try:
        return _tm().server_endpoint()
    except Exception:
        return None


class StepStallError(MXNetError):
    """A fused train step exceeded ``MXNET_STEP_TIMEOUT_S`` — the
    signature of a rank parked in a collective whose peer died without
    closing the socket.  Routed to the same rescale path as a detected
    death."""


class MembershipChange(MXNetError):
    """Raised at a step boundary when the elastic control plane sees a
    membership event (``kind='lost'``: stale heartbeats, ``{rank:
    age_s}``; ``kind='join'``: pending join requests, ``{nonce:
    record}``).  Control flow only — fit's elastic wrapper catches it
    and runs the rescale barrier."""

    def __init__(self, kind, info):
        super().__init__("elastic membership change: %s %r" % (kind, info))
        self.kind = kind
        self.info = info


def rescale_errors():
    """The exception tuple fit treats as 'the data or control plane
    says the membership changed': the step-boundary detection, the
    step watchdog, and the data plane's own collective failure
    (JaxRuntimeError — a gloo/ICI all-reduce fails within milliseconds
    of a peer death, usually the FIRST signal)."""
    import jax
    return (MembershipChange, StepStallError, jax.errors.JaxRuntimeError)


def call_bounded(fn, timeout_s, what="train step"):
    """Run ``fn()`` to completion or raise :class:`StepStallError`
    after ``timeout_s``.

    The body runs in a helper thread so the caller can give up on a
    stalled collective (the data plane offers no cancellation: a gloo/
    ICI all-reduce whose peer vanished without a FIN blocks forever).
    On timeout the helper thread is abandoned — it parks in the dead
    collective until teardown invalidates its runtime; that leak is
    the documented cost of the degraded path, paid once per stall.
    ``timeout_s <= 0`` disables the watchdog."""
    if not timeout_s or timeout_s <= 0:
        return fn()
    box = {}
    done = threading.Event()

    def _run():
        try:
            box["value"] = fn()
        except BaseException as e:   # noqa: BLE001 - reraised below
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_run, name="mxnet-step-watchdog",
                         daemon=True)
    t.start()
    if not done.wait(timeout_s):
        raise StepStallError(
            "%s did not complete within MXNET_STEP_TIMEOUT_S=%.1fs "
            "(a collective stalled on a dead peer?)" % (what, timeout_s))
    if "error" in box:
        raise box["error"]
    return box.get("value")


def free_port(host="127.0.0.1"):
    """Pick a currently-free TCP port on ``host`` (the classic bind-0
    race is acceptable: the port is consumed within the same rescale
    barrier round-trip)."""
    s = socket.socket()
    try:
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


def plan_microbatches(base_world, world, new_rank):
    """Part ownership after a rescale: ``base_world`` (B) dataset parts
    over ``world`` (W) members, A = B/W microbatches each.

    Member j owns parts ``[j, j+W, j+2W, ...]`` — microbatch ``a`` of
    the fused step covers parts ``[a*W, (a+1)*W)`` across the world,
    i.e. exactly the rows ranks ``a*W..(a+1)*W-1`` of the base world
    held.  The per-microbatch psum reproduces the base world's
    per-step reduction and the sequential accumulation fixes the
    cross-microbatch order, which is what makes the post-rescale
    params bitwise-identical to the unfaulted twin's.

    Returns ``(accum, owned_parts)``.  Raises when B % W != 0 — an
    uneven split would change per-microbatch reduction shapes and
    break the bitwise contract."""
    if base_world % world != 0:
        raise MXNetError(
            "elastic rescale needs the surviving world (%d) to divide "
            "the base world (%d): the global batch cannot be re-tiled "
            "bitwise otherwise" % (world, base_world))
    accum = base_world // world
    owned = tuple(new_rank + a * world for a in range(accum))
    return accum, owned


# ---------------------------------------------------------------------------
# file helpers
# ---------------------------------------------------------------------------

def _write_json(path, obj):
    tmp = "%s.%d.tmp" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.rename(tmp, path)


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None       # mid-rename / torn read: caller re-polls


class ElasticAgent(object):
    """One rank's view of the elastic membership protocol.

    Trainers construct it with their initial ``rank``/``world``; a
    relaunched process that wants back in constructs it with
    ``rank=None`` and calls :meth:`request_join` / :meth:`wait_plan`.
    """

    def __init__(self, root=None, rank=None, world=None, base_world=None,
                 host=None, dead_s=None, hb_s=None):
        self.root = root or _cfg("MXNET_ELASTIC_DIR")
        if not self.root:
            raise MXNetError("ElasticAgent needs MXNET_ELASTIC_DIR")
        self.rank = rank
        self.world = world
        self.base_world = base_world
        self.gen = 1
        self.dead_s = float(dead_s if dead_s is not None
                            else _cfg("MXNET_DIST_DEAD_S"))
        self.hb_s = float(hb_s if hb_s is not None
                          else _cfg("MXNET_ELASTIC_HB_S"))
        self.host = host or _cfg("MXNET_ELASTIC_HOST") or "127.0.0.1"
        self.step = (0, 0)            # last globally completed (epoch, nbatch)
        self.nonce = None             # join mode
        self._stop = threading.Event()
        self._thread = None
        self._gen_adopted_at = time.time()
        os.makedirs(self.root, exist_ok=True)

    # -- paths ------------------------------------------------------------
    def _hb_path(self, gen, rank):
        return os.path.join(self.root, "hb-g%d-r%d.json" % (gen, rank))

    def _vote_path(self, gen, rank):
        return os.path.join(self.root, "vote-g%d-r%d.json" % (gen, rank))

    def _plan_path(self, gen):
        return os.path.join(self.root, "plan-g%d.json" % gen)

    # -- lifecycle --------------------------------------------------------
    def start(self):
        """Publish cluster facts + first heartbeat, start the beat
        thread.  Call from every member once the initial world is up."""
        cpath = os.path.join(self.root, "cluster.json")
        if self.rank == 0 and not os.path.exists(cpath):
            _write_json(cpath, {"base_world": int(self.base_world
                                                  or self.world)})
        if self.base_world is None:
            c = _read_json(cpath)
            self.base_world = int(c["base_world"]) if c else self.world
        self._beat()
        self._thread = threading.Thread(target=self._beat_loop,
                                        name="mxnet-elastic-hb", daemon=True)
        self._thread.start()
        _tm().gauge("elastic/member_epoch",
                    "current elastic membership epoch").set(self.gen)
        _tm().gauge("elastic/world_size",
                    "current dist_tpu_sync world size").set(self.world or 0)
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.hb_s + 1)
            self._thread = None

    def _beat(self):
        now = time.time()
        if self.nonce is not None:
            _write_json(os.path.join(self.root, "join-%s.json" % self.nonce),
                        {"nonce": self.nonce, "pid": os.getpid(),
                         "host": self.host, "ts": now})
        elif self.rank is not None:
            rec = {"rank": self.rank, "pid": os.getpid(),
                   "host": self.host, "step": list(self.step),
                   "ts": now}
            # publish this rank's telemetry endpoint so the cluster
            # observatory (observatory.py) can discover and scrape it
            # with zero extra configuration — absent when no metrics
            # server is running in this process
            ep = _telemetry_endpoint()
            if ep:
                rec["telemetry"] = ep
            _write_json(self._hb_path(self.gen, self.rank), rec)

    def _beat_loop(self):
        while not self._stop.wait(self.hb_s):
            try:
                self._beat()
            except OSError as e:
                _log.warning("elastic heartbeat write failed: %s", e)

    def completed(self, epoch, nbatch):
        """Record the last globally completed step (call at every step
        boundary; rides the next heartbeat and the next vote)."""
        self.step = (int(epoch), int(nbatch))

    # -- observation ------------------------------------------------------
    def _hb_age(self, gen, rank, now=None):
        rec = _read_json(self._hb_path(gen, rank))
        if rec is None:
            # no heartbeat yet: age since this generation was adopted
            return (now or time.time()) - self._gen_adopted_at
        return (now or time.time()) - float(rec.get("ts", 0.0))

    def member_host(self, rank):
        rec = _read_json(self._hb_path(self.gen, rank))
        return (rec or {}).get("host", "127.0.0.1")

    def lost(self):
        """Ranks of the current generation whose heartbeat is stale.
        ``{rank: age_seconds}``; empty when everyone is live."""
        now = time.time()
        out = {}
        for r in range(self.world):
            if r == self.rank:
                continue
            age = self._hb_age(self.gen, r, now)
            if age > self.dead_s:
                out[r] = age
        return out

    def joiners(self):
        """Fresh join requests (nonce -> record), admission candidates
        for the next step boundary."""
        now = time.time()
        out = {}
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for n in sorted(names):
            if not (n.startswith("join-") and n.endswith(".json")):
                continue
            rec = _read_json(os.path.join(self.root, n))
            if rec and now - float(rec.get("ts", 0.0)) <= self.dead_s:
                out[rec["nonce"]] = rec
        return out

    # -- the rescale barrier ----------------------------------------------
    def rescale(self, admit_joiners=True, timeout=None):
        """Run the rescale barrier for the current generation and
        return the adopted plan.

        Every survivor calls this after detecting a membership change
        (a lost rank, or pending joiners at a step boundary).  The
        lowest-ranked live survivor acts as coordinator: it waits for
        every live survivor's vote, agrees the resume step (min), maps
        survivors (old-rank order) then joiners (nonce order) onto new
        ranks 0..W-1, picks a fresh coordinator port on its own host,
        and publishes the plan.  Everyone else polls for the plan.
        The barrier tolerates the coordinator itself dying mid-barrier
        (the next-lowest survivor takes over when its heartbeat goes
        stale)."""
        timeout = timeout or max(4 * self.dead_s, 20.0)
        deadline = time.time() + timeout
        gen = self.gen
        _write_json(self._vote_path(gen, self.rank),
                    {"rank": self.rank, "step": list(self.step),
                     "ts": time.time()})
        self._beat()
        while time.time() < deadline:
            plan = _read_json(self._plan_path(gen))
            if plan is not None:
                return self._adopt(plan)
            now = time.time()
            live = [r for r in range(self.world)
                    if r == self.rank
                    or self._hb_age(gen, r, now) <= self.dead_s]
            if live and min(live) == self.rank:
                plan = self._coordinate(gen, live, admit_joiners, deadline)
                if plan is not None:
                    return self._adopt(plan)
            time.sleep(min(self.hb_s, 0.1))
        raise MXNetError(
            "elastic rescale barrier timed out after %.1fs (gen %d): no "
            "plan agreed" % (timeout, gen))

    def _coordinate(self, gen, live, admit_joiners, deadline):
        """Coordinator body: collect votes from every live survivor,
        then publish the plan.  Returns None when demoted (a
        lower-ranked survivor reappeared)."""
        while time.time() < deadline:
            now = time.time()
            live = [r for r in range(self.world)
                    if r == self.rank
                    or self._hb_age(gen, r, now) <= self.dead_s]
            if min(live) != self.rank:
                return None
            votes = {}
            for r in live:
                v = _read_json(self._vote_path(gen, r))
                if v is not None:
                    votes[r] = tuple(int(x) for x in v["step"])
            if len(votes) == len(live):
                step = min(votes.values())
                joiners = self.joiners() if admit_joiners else {}
                members = []
                for new_rank, old in enumerate(sorted(votes)):
                    members.append({
                        "rank": new_rank, "old": old, "joiner": None,
                        "host": (self.host if old == self.rank
                                 else self.member_host(old))})
                for off, nonce in enumerate(sorted(joiners)):
                    members.append({
                        "rank": len(votes) + off, "old": None,
                        "joiner": nonce,
                        "host": joiners[nonce].get("host", "127.0.0.1")})
                plan = {
                    "gen": gen + 1,
                    "world": len(members),
                    "members": members,
                    "coordinator": "%s:%d" % (self.host,
                                              free_port(self.host)),
                    "step": list(step),
                    "base_world": int(self.base_world),
                    "grow": len(members) > len(votes),
                    "ts": time.time(),
                }
                _write_json(self._plan_path(gen), plan)
                self._gc(gen)
                return plan
            time.sleep(min(self.hb_s, 0.1))
        return None

    def _adopt(self, plan):
        """Take on my identity in the new generation and heartbeat it
        immediately (so peers' liveness scans see the new world)."""
        me = None
        for m in plan["members"]:
            if self.nonce is not None and m.get("joiner") == self.nonce:
                me = m
                break
            if self.nonce is None and m.get("old") == self.rank:
                me = m
                break
        if me is None:
            raise MXNetError(
                "elastic plan for gen %d does not include this rank "
                "(old rank %s, nonce %s) — it was voted out of the "
                "membership" % (plan["gen"], self.rank, self.nonce))
        if self.nonce is not None:
            try:
                os.unlink(os.path.join(self.root,
                                       "join-%s.json" % self.nonce))
            except OSError:
                pass
            self.nonce = None
        self.rank = int(me["rank"])
        self.world = int(plan["world"])
        self.base_world = int(plan["base_world"])
        self.gen = int(plan["gen"])
        self.step = tuple(int(x) for x in plan["step"])
        self._gen_adopted_at = time.time()
        self._beat()
        _tm().gauge("elastic/member_epoch",
                    "current elastic membership epoch").set(self.gen)
        _tm().gauge("elastic/world_size",
                    "current dist_tpu_sync world size").set(self.world)
        return plan

    def _gc(self, gen):
        """Best-effort cleanup of generation ``gen``'s barrier files
        (coordinator only; losing a race to a crashed peer is fine)."""
        try:
            for n in os.listdir(self.root):
                if n.startswith(("vote-g%d-" % gen, "hb-g%d-" % gen)):
                    try:
                        os.unlink(os.path.join(self.root, n))
                    except OSError:
                        pass
        except OSError:
            pass

    # -- join mode --------------------------------------------------------
    def request_join(self, nonce=None):
        """Ask the running world to admit this process at its next step
        boundary.  Starts heartbeating the join request."""
        self.nonce = nonce or ("%d-%d" % (os.getpid(),
                                          int(time.time() * 1000)))
        c = _read_json(os.path.join(self.root, "cluster.json"))
        if c:
            self.base_world = int(c["base_world"])
        self._beat()
        if self._thread is None:
            self._thread = threading.Thread(target=self._beat_loop,
                                            name="mxnet-elastic-hb",
                                            daemon=True)
            self._thread.start()
        return self.nonce

    def wait_plan(self, timeout=120.0):
        """Joiner side of the barrier: wait for a plan that admits this
        nonce, adopt it, return it."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            latest = None
            try:
                names = os.listdir(self.root)
            except OSError:
                names = []
            for n in names:
                if n.startswith("plan-g") and n.endswith(".json"):
                    p = _read_json(os.path.join(self.root, n))
                    if p and any(m.get("joiner") == self.nonce
                                 for m in p["members"]):
                        if latest is None or p["gen"] > latest["gen"]:
                            latest = p
            if latest is not None:
                return self._adopt(latest)
            time.sleep(0.1)
        raise MXNetError("join request %s not admitted within %.0fs"
                         % (self.nonce, timeout))


class ElasticFit(object):
    """fit()-side driver for elastic ``dist_tpu_sync`` training.

    Owns the :class:`ElasticAgent`, the 2-deep step-boundary host
    mirror ring (params + optimizer state, keyed by completed
    ``(epoch, nbatch)``), the step watchdog, and the full rescale
    sequence: barrier → runtime reinit → input reshard → module
    rebuild → seek.  BaseModule.fit calls four hooks per step
    (:meth:`pre_step`, :meth:`run_update`, :meth:`note_step`) and
    routes any :func:`rescale_errors` exception to :meth:`handle`,
    which returns the ``(epoch, nbatch)`` to re-enter the loop at.
    """

    def __init__(self, agent, kv_type="dist_tpu_sync"):
        self.agent = agent
        self.kv_type = kv_type
        self.module = None
        self.train_data = None
        self.accum = 1
        self.owned = None
        self.step_timeout = float(_cfg("MXNET_STEP_TIMEOUT_S"))
        self._mirrors = {}          # (epoch, completed) -> snapshot
        self._pending_opt = None    # joiner: plan gen to pull opt state of

    # -- construction ------------------------------------------------------
    @classmethod
    def for_world(cls, module, train_data, kv):
        """Driver for a founding member (fit with a live dist kvstore)."""
        agent = ElasticAgent(rank=kv.rank, world=kv.num_workers).start()
        drv = cls(agent, kv_type=kv.type)
        drv.module = module
        drv.train_data = train_data
        return drv

    @classmethod
    def join(cls, train_data, timeout=120.0):
        """Joiner pre-phase, run BEFORE fit binds: request admission,
        adopt the published plan, bring the runtime up against the new
        coordinator, reshard + seek the iterator.  Returns ``(driver,
        begin_epoch, skip_nbatch)`` — fit then proceeds through its
        normal bind/init path (the kvstore init broadcast pulls the
        survivors' parameters) and calls :meth:`after_init`."""
        from . import dist_runtime as _dist
        agent = ElasticAgent()
        agent.request_join()
        plan = agent.wait_plan(timeout=timeout)
        _dist.reinit(plan["coordinator"], int(plan["world"]),
                     int(agent.rank))
        drv = cls(agent)
        drv.train_data = train_data
        drv.accum, drv.owned = plan_microbatches(
            agent.base_world, agent.world, agent.rank)
        if hasattr(train_data, "elastic_reshard"):
            train_data.elastic_reshard(agent.base_world, drv.owned)
        epoch, nbatch = agent.step
        if hasattr(train_data, "restore_state"):
            train_data.restore_state({"epoch": epoch, "batch": nbatch})
        drv._pending_opt = int(plan["gen"])
        return drv, epoch, nbatch

    def after_init(self, module, begin_epoch=0, skip_nbatch=0):
        """Once fit's init_optimizer is done: install the accum factor,
        adopt the survivors' optimizer state (joiners), capture the
        first mirror."""
        self.module = module
        if self.accum > 1 and hasattr(module, "_elastic_accum"):
            module._elastic_accum = int(self.accum)
        if self._pending_opt is not None:
            blob = self._wait_opt_blob(self._pending_opt)
            if blob is not None and \
                    getattr(module, "_updater", None) is not None:
                module._updater.set_states(blob["updater"])
                if blob.get("opt_counts") is not None:
                    module._optimizer._index_update_count = \
                        dict(blob["opt_counts"])
                    module._optimizer.num_update = int(blob["num_update"])
            self._pending_opt = None
        self.note_step(begin_epoch, skip_nbatch)

    def stop(self):
        self.agent.stop()

    # -- per-step hooks ----------------------------------------------------
    def pre_step(self, epoch, nbatch):
        """Top of each training step, after the previous step's mirror
        was captured: the armed-fault window and the heartbeat scan."""
        from . import fault as _fault
        _fault.inject("dist.member")
        lost = self.agent.lost()
        if lost:
            raise MembershipChange("lost", lost)
        joiners = self.agent.joiners()
        if joiners:
            raise MembershipChange("join", joiners)

    def run_update(self):
        """module.update() under the step watchdog: a collective parked
        on a dead peer that never closed its socket surfaces as
        :class:`StepStallError` instead of hanging forever."""
        return call_bounded(self.module.update, self.step_timeout,
                            what="fused train step")

    def note_step(self, epoch, completed):
        """A step completed globally: record it for the next vote and
        mirror the module state (the asnumpy copies double as the
        step-completion sync point)."""
        self.agent.completed(epoch, completed)
        self._mirrors[(int(epoch), int(completed))] = \
            self.module.elastic_snapshot()
        while len(self._mirrors) > 2:
            del self._mirrors[min(self._mirrors)]

    # -- the rescale -------------------------------------------------------
    def _mirror_for(self, epoch, nbatch):
        key = (int(epoch), int(nbatch))
        if key in self._mirrors:
            return self._mirrors[key]
        older = [k for k in self._mirrors if k <= key]
        if not older:
            raise MXNetError(
                "no elastic mirror at or before step %r (have %r) — "
                "cannot restore the agreed state"
                % (key, sorted(self._mirrors)))
        return self._mirrors[max(older)]

    def handle(self, exc):
        """The full rescale: flight-record the detection, run the
        barrier, reinit the runtime over the plan's membership, reshard
        the input, rebuild the module from the agreed step's mirror.
        Returns ``(epoch, nbatch)`` for fit to re-enter its loop at."""
        from . import blackbox as _bb
        from . import dist_runtime as _dist
        from . import fault as _fault
        tm = _tm()
        agent = self.agent
        old_world = agent.world
        t0 = time.monotonic()
        if isinstance(exc, MembershipChange) and exc.kind == "join":
            _log.info("elastic: admitting joiners %s",
                      sorted(exc.info))
        else:
            source = ("step-watchdog" if isinstance(exc, StepStallError)
                      else "stale-heartbeat"
                      if isinstance(exc, MembershipChange)
                      else "collective-error")
            lost = exc.info if isinstance(exc, MembershipChange) \
                else agent.lost()
            if lost:
                for r, age in sorted(lost.items()):
                    _bb.record_event("member_lost", rank=int(r),
                                     source=source,
                                     hb_age_s=round(float(age), 3))
                tm.histogram(
                    "elastic/detect_seconds",
                    "seconds from a rank's last heartbeat to its loss "
                    "being declared").observe(max(lost.values()))
            else:
                # the data plane failed before any heartbeat went stale
                # (gloo fails in milliseconds); no rank named yet
                _bb.record_event("member_lost", rank=-1, source=source,
                                 hb_age_s=-1.0)
            tm.counter("elastic/member_lost_total",
                       "ranks declared lost by the elastic control "
                       "plane").inc(max(len(lost), 1))
            _log.warning("elastic: membership change (%s): %s",
                         source, exc)
        _fault.inject("dist.rescale")
        plan = agent.rescale(admit_joiners=True)
        _dist.reinit(plan["coordinator"], int(plan["world"]),
                     int(agent.rank))
        self.accum, self.owned = plan_microbatches(
            agent.base_world, agent.world, agent.rank)
        epoch, nbatch = agent.step
        if agent.rank == 0 and plan.get("grow"):
            # joiners have no optimizer state to restore from; publish
            # the agreed step's (before their init_optimizer completes,
            # which the joint kv init broadcast serializes anyway)
            self._write_opt_blob(int(plan["gen"]),
                                 self._mirror_for(epoch, nbatch))
        td = self.train_data
        if hasattr(td, "elastic_reshard"):
            td.elastic_reshard(agent.base_world, self.owned)
        self.module.elastic_restore(
            self._mirror_for(epoch, nbatch), td.provide_data,
            getattr(td, "provide_label", None) or None,
            kvstore=self.kv_type, accum=self.accum)
        if hasattr(td, "restore_state"):
            td.restore_state({"epoch": epoch, "batch": nbatch})
        wall = time.monotonic() - t0
        # goodput: the whole outage window — from the failing step's
        # start through detection, barrier, reinit, reshard, restore —
        # is unaccounted (the step never reached step_end); close it
        # into the `rescale` category (compile deltas stay in `compile`)
        try:
            from . import goodput as _gp
            _gp.note_since_last("rescale")
        except Exception:
            pass
        _bb.record_event("rescale", old_world=int(old_world),
                         world=int(agent.world), gen=int(agent.gen),
                         epoch=int(epoch), nbatch=int(nbatch),
                         accum=int(self.accum),
                         grow=bool(plan.get("grow")),
                         wall_s=round(wall, 3))
        tm.counter("elastic/rescales_total",
                   "completed elastic rescales (shrink or grow)").inc()
        tm.histogram("elastic/rescale_seconds",
                     "wall seconds from detection to the rebuilt "
                     "module (barrier + runtime reinit + reshard + "
                     "restore)").observe(wall)
        self._mirrors = {k: v for k, v in self._mirrors.items()
                         if k <= (epoch, nbatch)}
        _log.info("elastic: rescaled to world=%d gen=%d accum=%d, "
                  "resuming at epoch %d batch %d (%.2fs)", agent.world,
                  agent.gen, self.accum, epoch, nbatch, wall)
        return epoch, nbatch

    # -- joiner optimizer-state transfer ----------------------------------
    def _opt_blob_path(self, gen):
        return os.path.join(self.agent.root, "opt-g%d.bin" % gen)

    def _write_opt_blob(self, gen, snap):
        import pickle
        path = self._opt_blob_path(gen)
        tmp = "%s.%d.tmp" % (path, os.getpid())
        with open(tmp, "wb") as f:
            f.write(pickle.dumps({
                "updater": snap.get("updater"),
                "opt_counts": snap.get("opt_counts"),
                "num_update": snap.get("num_update", 0)}))
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, path)

    def _wait_opt_blob(self, gen, timeout=60.0):
        import pickle
        path = self._opt_blob_path(gen)
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                with open(path, "rb") as f:
                    return pickle.loads(f.read())
            except (OSError, EOFError, pickle.UnpicklingError):
                time.sleep(0.05)
        _log.warning("elastic: optimizer-state blob %s never appeared; "
                     "joining with fresh optimizer state", path)
        return None
