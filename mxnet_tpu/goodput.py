"""Training goodput ledger: attribute every wall-second to ONE category.

The per-process observability stack (telemetry/tracing/health) answers
*what is this process doing right now*; the goodput ledger answers the
cost-accounting question a pods-as-cattle training fleet lives or dies
by: **what fraction of the run's wall-clock was useful training
compute**, and where exactly did the rest go. Every wall-second of a
session is attributed to exactly one of :data:`CATEGORIES`:

* ``step_compute`` — inside a training step, net of everything below:
  the goodput numerator.
* ``data_wait`` — the training loop blocked on the input iterator
  (the ``train.data_wait`` span's interval, measured at the source).
* ``compile`` — XLA backend compile wall, read as deltas of the
  ``jax.monitoring`` compile listener's cumulative total
  (:func:`telemetry.compile_time`) so cost-analysis pseudo-compiles
  stay fenced out exactly like the compile counters.
* ``checkpoint`` — fit-loop checkpoint saves (the ``train.checkpoint``
  span's interval).
* ``rescale`` — the elastic outage window: from the last accounted
  instant (the failing step's start) through member-loss detection,
  barrier re-rendezvous, runtime reinit, and mirror restore
  (``ElasticFit.handle``'s whole wall, compile deltas excluded — the
  post-reshard program rebuild lands in ``compile``).
* ``restart`` — the supervisor relaunch gap: a relaunched process finds
  its predecessor's death timestamp in
  ``MXNET_GOODPUT_PREV_EXIT_TS`` (stamped by
  :class:`~mxnet_tpu.checkpoint.ProcessSupervisor`) and books the
  dead time before its own session started.
* ``straggler_wait`` — time parked at a distributed rendezvous waiting
  for slower ranks (the ``kv.barrier_wait`` interval).
* ``idle`` — the closing residual; never booked directly.

**Hard invariant**: the categories sum to the measured wall — ``idle``
is defined as the residual, and if booked time ever exceeds wall
(clock skew between accounting points) every category is scaled down
proportionally so the report still sums exactly; the overrun is
reported honestly as ``overrun_s`` instead of silently corrupting a
category. ``tools/check_metrics_docs.py`` drift-checks the category
names here against the taxonomy table in docs/observability.md.

Cost model: the ledger is pure host arithmetic — two ``perf_counter``
reads and a few dict adds per step, **zero** extra device dispatches
(tests/test_observatory.py holds the dispatch count equal with the
ledger on and off). ``MXNET_GOODPUT=0`` removes the fit
hooks behind one module bool.

Surfaces: ``goodput/*`` gauges on ``/metrics``, :func:`report` (also
embedded in ``mxnet_tpu.diagnostics()`` and banked into every bench
record via ``telemetry.snapshot()``), and the default
``badput_fraction`` SLO rule on the ``goodput/badput_fraction`` gauge.
"""
from __future__ import annotations

import threading
import time

__all__ = ["CATEGORIES", "session_begin", "session_end", "active",
           "step_begin", "step_end", "note", "note_since_last",
           "report", "reset", "enabled", "enable"]

_monotonic = time.perf_counter

# the complete attribution taxonomy — every wall-second of a session
# lands in exactly one of these (idle is the closing residual).
# Drift-checked against the docs/observability.md goodput-categories
# table by tools/check_metrics_docs.py.
CATEGORIES = ("step_compute", "data_wait", "compile", "checkpoint",
              "rescale", "restart", "straggler_wait", "idle")


def _config_enabled():
    try:
        from .config import get
        return bool(get("MXNET_GOODPUT"))
    except Exception:
        return True


_enabled = _config_enabled()


def enabled():
    return _enabled


def enable(on=True):
    """Turn the ledger hooks on/off (also: ``MXNET_GOODPUT=0``).
    Returns the previous state; an active session keeps accumulating
    only while enabled."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    return prev


class _Ledger(object):
    """One session's attribution state. All booked categories are
    absolute seconds; ``idle`` is computed at report time as the
    residual against measured wall."""

    def __init__(self):
        self.lock = threading.Lock()
        self.t0 = None             # perf_counter at session start
        self.base_wall = 0.0       # pre-session wall credited (restart gap)
        self.booked = {}           # category -> seconds (never "idle")
        self.mark = None           # perf instant of last full accounting
        self.compile_seen = 0.0    # telemetry.compile_time() watermark
        self.steps = 0
        self.step_open = False     # between step_begin and step_end
        self.step_overlap = 0.0    # seconds note()d inside the open step
                                   # (barrier waits, checkpoint saves):
                                   # subtracted from that step's compute
                                   # so nothing is double-counted

    def active(self):
        return self.t0 is not None

    def wall_s(self, now=None):
        if self.t0 is None:
            return 0.0
        return ((now if now is not None else _monotonic())
                - self.t0) + self.base_wall

    def _book(self, category, seconds):
        if seconds > 0:
            self.booked[category] = self.booked.get(category, 0.0) + seconds

    def _sync_compile(self):
        """Book the compile-listener delta since the last accounting
        point into ``compile`` and return it (callers subtract it from
        the interval they are about to attribute, so compile wall is
        never double-counted)."""
        try:
            from . import telemetry as _tm
            total = _tm.compile_time()
        except Exception:
            return 0.0
        delta = total - self.compile_seen
        self.compile_seen = total
        if delta > 0:
            self._book("compile", delta)
            return delta
        return 0.0


_L = _Ledger()


def reset():
    """Drop the session (test isolation)."""
    global _L
    _L = _Ledger()


def active():
    return _L.active()


def session_begin():
    """Start (or no-op into) the ledger session. Reads
    ``MXNET_GOODPUT_PREV_EXIT_TS`` — stamped into a relaunched child's
    env by :class:`~mxnet_tpu.checkpoint.ProcessSupervisor` — and books
    the supervisor relaunch gap as ``restart``, extending measured wall
    by the same amount so the invariant covers the outage."""
    if not _enabled:
        return
    with _L.lock:
        if _L.t0 is not None:
            return
        _L.t0 = _monotonic()
        _L.mark = _L.t0
        try:
            from . import telemetry as _tm
            _L.compile_seen = _tm.compile_time()
        except Exception:
            _L.compile_seen = 0.0
        try:
            from .config import get as _cfg
            prev = float(_cfg("MXNET_GOODPUT_PREV_EXIT_TS") or 0.0)
        except Exception:
            prev = 0.0
        if prev > 0:
            gap = time.time() - prev
            if gap > 0:
                _L.base_wall += gap
                _L._book("restart", gap)
    _update_gauges()


def session_end():
    """Close the session: flush pending compile wall and push final
    gauges. The ledger stays readable (``report()``) until reset."""
    if _L.t0 is None:
        return
    with _L.lock:
        _L._sync_compile()
        _L.mark = _monotonic()
    _update_gauges()


def step_begin():
    """Start-of-step token for the fit loop (perf instant)."""
    if not _enabled or _L.t0 is None:
        return None
    with _L.lock:
        _L.step_open = True
        _L.step_overlap = 0.0
    return _monotonic()


def step_end(token, data_wait_s=0.0, straggler_s=0.0):
    """Account one finished training step: the step window minus the
    compile delta observed during it, minus the measured data wait and
    rendezvous wait, is ``step_compute``."""
    if token is None or not _enabled or _L.t0 is None:
        return
    now = _monotonic()
    with _L.lock:
        cdelta = _L._sync_compile()
        if data_wait_s > 0:
            _L._book("data_wait", data_wait_s)
        if straggler_s > 0:
            _L._book("straggler_wait", straggler_s)
        _L._book("step_compute",
                 max(0.0, (now - token) - cdelta - max(0.0, data_wait_s)
                     - max(0.0, straggler_s) - _L.step_overlap))
        _L.step_open = False
        _L.step_overlap = 0.0
        _L.mark = now
        _L.steps += 1
        steps = _L.steps
    # gauges serve periodic scrapes — refreshing every 8th step keeps
    # the per-step hook to two clock reads + dict adds (what the hook
    # costs a step on the chip: not measured)
    if steps % 8 == 0:
        _update_gauges()


def note(category, seconds):
    """Book an externally measured interval (checkpoint saves,
    rendezvous waits). ``category`` must be a member of
    :data:`CATEGORIES` other than ``idle``."""
    if not _enabled or _L.t0 is None or seconds <= 0:
        return
    if category not in CATEGORIES or category == "idle":
        raise ValueError("unknown goodput category %r" % (category,))
    with _L.lock:
        _L._book(category, float(seconds))
        if _L.step_open:
            # booked from inside an open step window (a barrier wait in
            # train.update, a mid-step checkpoint): remember it so
            # step_end keeps step_compute disjoint
            _L.step_overlap += float(seconds)


def note_since_last(category):
    """Book everything since the last accounting point into
    ``category`` (compile deltas excluded — they stay in ``compile``).
    This is how the elastic outage window lands in ``rescale``: the
    failing step never reaches ``step_end``, so the stretch from its
    start through detection + re-rendezvous is unaccounted until
    ``ElasticFit.handle`` closes it here."""
    if not _enabled or _L.t0 is None:
        return 0.0
    if category not in CATEGORIES or category == "idle":
        raise ValueError("unknown goodput category %r" % (category,))
    now = _monotonic()
    with _L.lock:
        cdelta = _L._sync_compile()
        dt = max(0.0, (now - (_L.mark if _L.mark is not None else now))
                 - cdelta)
        _L._book(category, dt)
        _L.mark = now
        # an interrupted step (the failing collective) never reaches
        # step_end; its window was just accounted here
        _L.step_open = False
        _L.step_overlap = 0.0
    _update_gauges()
    return dt


def report():
    """The ledger, closed against measured wall. Categories (including
    the ``idle`` residual) sum to ``wall_s`` exactly; if booked time
    exceeded wall, every category is scaled proportionally and the
    overage is reported as ``overrun_s``."""
    with _L.lock:
        if _L.t0 is None:
            return {"active": False}
        now = _monotonic()
        wall = _L.wall_s(now)
        booked = dict(_L.booked)
        steps = _L.steps
    total_booked = sum(booked.values())
    overrun = 0.0
    if wall <= 0:
        wall = max(wall, 1e-9)
    if total_booked > wall:
        overrun = total_booked - wall
        scale = wall / total_booked
        booked = {k: v * scale for k, v in booked.items()}
        total_booked = wall
    booked["idle"] = wall - total_booked
    cats = {}
    for c in CATEGORIES:
        s = booked.get(c, 0.0)
        cats[c] = {"seconds": round(s, 6), "fraction": round(s / wall, 6)}
    good = booked.get("step_compute", 0.0) / wall
    return {"active": True,
            "wall_s": round(wall, 6),
            "steps": steps,
            "categories": cats,
            "goodput_fraction": round(good, 6),
            "badput_fraction": round(1.0 - good, 6),
            "overrun_s": round(overrun, 6)}


def _update_gauges():
    """Mirror the ledger into ``goodput/*`` gauges (cheap dict sets;
    skipped entirely with telemetry off)."""
    try:
        from . import telemetry as _tm
        if not _tm._enabled or _L.t0 is None:
            return
        rep = report()
        _tm.gauge("goodput/wall_seconds",
                  "Measured wall of the goodput-ledger session "
                  "(includes any credited supervisor restart gap)"
                  ).set(rep["wall_s"])
        g = _tm.gauge("goodput/category_seconds",
                      "Wall seconds attributed per goodput category "
                      "(categories sum to goodput/wall_seconds)",
                      ("category",))
        for c in CATEGORIES:
            g.labels(c).set(rep["categories"][c]["seconds"])
        _tm.gauge("goodput/goodput_fraction",
                  "Fraction of session wall spent in useful training "
                  "step compute").set(rep["goodput_fraction"])
        _tm.gauge("goodput/badput_fraction",
                  "1 - goodput fraction: the default badput_fraction "
                  "SLO rule watches this").set(rep["badput_fraction"])
    except Exception:
        pass
