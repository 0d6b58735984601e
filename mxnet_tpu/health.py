"""Production health layer: live roofline accounting, numerics
sentinels, and an SLO alert engine.

PRs 1 and 5 made the stack *measurable* (metrics everywhere, span
tracing everywhere); this module makes it *self-watching* — the three
active pillars, plus the crash-safe flight recorder in blackbox.py:

1. **Live MFU / roofline accounting** — every compiled hot-path
   program (executor forward jits, the fused train step, the serve
   bucket ladder, decode prefill/step) registers its XLA cost analysis
   (FLOPs + bytes accessed, from ``jitted.lower(...).cost_analysis()``
   — an HLO cost pass, NOT a second backend compile) at compile time;
   measured step wall times then turn into ``executor/mfu`` /
   ``executor/hbm_bw_util`` and per-serve-bucket equivalents on
   ``/metrics``. The FLOP number is *measured from the program* by
   XLA's own cost pass, never hand-counted, so a gauge cannot drift
   from the code it prices when a layer or a dtype changes. Where
   the backend returns no analysis the capture
   degrades to an ``unavailable`` counter and the gauges simply never
   appear (the documented n/a fallback).
2. **Numerics sentinels** — ``MXNET_NUMERICS=off|step|full`` folds a
   loss proxy, the global gradient norm, and nonfinite counts into the
   SAME donated XLA program as the fused train step (executor.py):
   zero extra host dispatches, zero recompiles across LR-schedule
   steps; ``full`` adds per-parameter attribution so a trip names the
   layer. :func:`check_numerics` applies the policy
   (``warn | raise | checkpoint-and-raise``) and leaves a flight-
   recorder record before anything else can die.
3. **SLO engine** — declarative :func:`watch` rules evaluated by one
   background daemon thread with multi-window burn-rate semantics (a
   rule fires only when the violation fraction exceeds its burn
   threshold over BOTH the short and the long window — a blip can't
   page, a sustained regression can't hide), surfaced at ``/alerts``
   on both ``telemetry.serve()`` and ``serve.serve_http``; every
   transition is recorded as a span, a counter, and a flight-recorder
   event.

Cost model: nothing here sits on a per-dispatch hot path. Cost capture
runs once per compiled program at compile/warmup time; MFU gauge
updates are a few float ops per *step*; the sentinel's per-step cost
is one small-array D2H fetch (not an op dispatch, not a recompile);
the SLO evaluator wakes every ``MXNET_SLO_INTERVAL_S`` seconds and
only ever *reads* telemetry.
"""
from __future__ import annotations

import logging
import math
import threading
import time
from collections import deque

from .base import MXNetError

__all__ = ["NumericsError", "capture_cost", "register_cost",
           "program_cost", "programs",
           "note_executor_step", "note_serve_batch", "note_decode",
           "DEVICE_PEAKS", "device_peaks",
           "peak_flops", "peak_hbm_bytes_per_s",
           "mfu_summary",
           "numerics_mode", "set_numerics", "numerics_policy",
           "set_numerics_policy", "set_spike_factor", "check_numerics",
           "numerics_trips", "watch", "unwatch", "rules",
           "evaluate_once", "alerts_payload", "alerts_endpoint",
           "alerts_firing", "ensure_evaluator", "set_interval",
           "stop_evaluator", "reset"]

_monotonic = time.perf_counter
_log = logging.getLogger("mxnet_tpu.health")


def _config(name, fallback):
    try:
        from .config import get
        v = get(name)
        return fallback if v is None else v
    except Exception:
        return fallback


def _tm():
    from . import telemetry
    return telemetry


# ---------------------------------------------------------------------------
# pillar 1: roofline accounting from compiled cost analysis
# ---------------------------------------------------------------------------

# (kind, key) -> {"flops", "bytes", "captured_s"} | None (= capture
# attempted and unavailable on this backend: don't retry per call).
# This table is the diagnostics/aggregation view; the AUTHORITATIVE
# record for a program is the one its owner (executor, engine) holds —
# owners pass records by reference, so eviction here never skews a
# gauge. Bounded: oldest entries drop past _COSTS_CAP (long-lived
# serving with repeated swaps must not grow it without bound).
_costs = {}
_costs_lock = threading.Lock()
_COSTS_CAP = 512
_seq = 0


def next_cost_key(prefix):
    """A process-unique cost key (``prefix:N``). Callers must NOT key
    captures by ``id(self)`` — CPython reuses addresses after GC, and
    a reused id would make capture_cost hand a dead program's record
    to a new one."""
    global _seq
    with _costs_lock:
        _seq += 1
        return "%s:%d" % (prefix, _seq)

_KINDS = ("executor_forward", "fused_step", "serve_bucket",
          "decode_prefill", "decode_step")


# Published peaks of ONE chip, keyed by ``jax.Device.device_kind`` —
# the single table behind every live MFU / roofline gauge (the benchmark
# prices its readings from bench/peaks.json). Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s
# HBM; the chip reports itself as "TPU v5 lite" (read off the device,
# PR 21). v5e has no separate fp32 systolic path — under JAX's default
# precision fp32 matmuls run the MXU with bf16 operands — so the bf16
# peak is the fp32 denominator too. A kind not listed here has NO
# peak: the live gauges stay unset; a default would price another
# device's run with a v5e's roof.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def device_peaks(device=None):
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: the first
    JAX device), or None for a ``device_kind`` the table does not
    hold."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return DEVICE_PEAKS.get(device.device_kind)


def peak_flops():
    """Peak FLOP/s of the attached chip, or None (unknown kind)."""
    row = device_peaks()
    return row["flops"] if row else None


def peak_hbm_bytes_per_s():
    """Peak HBM bandwidth of the attached chip, or None."""
    row = device_peaks()
    return row["hbm_bytes_per_s"] if row else None


def capture_cost(kind, key, jitted, args, kwargs=None, pkey=None):
    """Register the XLA cost analysis of one compiled program.

    ``jitted.lower(*args)`` traces + lowers (NO backend compile) and
    ``cost_analysis()`` runs XLA's HLO cost pass over the module —
    milliseconds even for programs whose real compile takes seconds.
    The few pseudo-compile events the pass itself emits are suppressed
    from the telemetry compile counters (they would poison the
    zero-recompile assertions every serving test banks).

    ``pkey`` (optional) is the site's registry :class:`ProgramKey`:
    when given and ``MXNET_FORENSICS`` is on, the compiler-forensics
    layer rides this same choke point to capture the program's
    optimized HLO (forensics.maybe_capture — once per fingerprint,
    same suppress fence, never raises back into the site).

    Returns the stored record, or None when the backend offers no
    analysis (counted in ``health/cost_analysis_unavailable_total`` —
    the documented n/a fallback: the MFU gauges simply never appear).
    """
    if kind not in _KINDS:
        raise MXNetError("unknown cost kind %r (known: %s)"
                         % (kind, ", ".join(_KINDS)))
    ck = (kind, str(key))
    with _costs_lock:
        if ck in _costs:
            return _costs[ck]
    tm = _tm()
    rec = None
    lowered = None
    try:
        with tm.suppress_compile_tracking():
            lowered = jitted.lower(*args, **(kwargs or {}))
            ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        flops = float(ca.get("flops", 0.0)) if ca else 0.0
        nbytes = float(ca.get("bytes accessed", 0.0)) if ca else 0.0
        if flops > 0:
            rec = {"flops": flops, "bytes": nbytes,
                   "captured_s": round(time.time(), 3)}
    except Exception as e:          # backend without cost analysis
        _log.debug("cost_analysis unavailable for %s/%s: %s",
                   kind, key, e)
    with _costs_lock:
        _costs[ck] = rec
        while len(_costs) > _COSTS_CAP:
            _costs.pop(next(iter(_costs)))
    if rec is None:
        if tm._enabled:
            tm.counter("health/cost_analysis_unavailable_total",
                       "Compiled programs whose backend returned no "
                       "cost analysis (MFU gauges degrade to absent)",
                       ("kind",)).labels(kind).inc()
    elif tm._enabled:
        tm.counter("health/programs_captured_total",
                   "Compiled programs with cost analysis registered "
                   "(flops + bytes accessed)", ("kind",)).labels(kind).inc()
    if pkey is not None:
        try:
            from . import forensics as _fx
            _fx.maybe_capture(pkey, jitted, args, kwargs, cost=rec,
                              lowered=lowered)
        except Exception as e:      # never let forensics break a site
            _log.debug("forensics capture failed for %s/%s: %s",
                       kind, key, e)
    return rec


def register_cost(kind, key, rec):
    """Alias an already-captured record under another (kind, key) —
    the serve engine maps its batch bucket onto the bound executor's
    forward-program capture instead of lowering the module twice."""
    if kind not in _KINDS:
        raise MXNetError("unknown cost kind %r (known: %s)"
                         % (kind, ", ".join(_KINDS)))
    with _costs_lock:
        _costs[(kind, str(key))] = rec
        while len(_costs) > _COSTS_CAP:
            _costs.pop(next(iter(_costs)))
    return rec


def program_cost(kind, key):
    """The stored cost record for one program, or None."""
    with _costs_lock:
        return _costs.get((kind, str(key)))


def programs():
    """Snapshot of every captured program: {(kind, key): record}."""
    with _costs_lock:
        return dict(_costs)


def _util(rec, seconds):
    """(mfu, hbm_bw_util) of one program execution, or None — also
    for a device whose peaks are unknown: no gauge, never a default."""
    row = device_peaks()
    if rec is None or seconds is None or seconds <= 0 or row is None:
        return None
    return (rec["flops"] / seconds / row["flops"],
            rec["bytes"] / seconds / row["hbm_bytes_per_s"])


def note_executor_step(rec, seconds):
    """Bank one measured fused-step wall time against its program's
    cost record: sets ``executor/mfu`` and ``executor/hbm_bw_util``."""
    util = _util(rec, seconds)
    if util is None:
        return None
    tm = _tm()
    if tm._enabled:
        tm.gauge("executor/mfu",
                 "Model FLOP/s utilization of the fused train step "
                 "(measured cost_analysis FLOPs / step wall / "
                 "the chip's peak, health.DEVICE_PEAKS)").set(util[0])
        tm.gauge("executor/hbm_bw_util",
                 "HBM roofline utilization of the fused train step "
                 "(bytes accessed / step wall / peak bandwidth)"
                 ).set(util[1])
    return util


def note_serve_batch(bucket, seconds, rec):
    """Per-serve-bucket MFU from one executed batch's compute wall.
    ``rec`` is the OWNING engine's cost record for this bucket (passed
    by reference, never looked up globally: with two live engines —
    shadow A/B, or the draining old engine during a swap — a global
    bucket lookup would price one engine's batches with the other's
    FLOPs). The gauge label is still just the bucket: concurrent
    engines last-writer-win the gauge, but each write is priced with
    its own program's cost."""
    util = _util(rec, seconds)
    if util is None:
        return None
    tm = _tm()
    if tm._enabled:
        tm.gauge("serving/mfu",
                 "Per-bucket MFU of the serve forward (measured FLOPs "
                 "/ compute wall / peak)", ("bucket",)
                 ).labels(str(bucket)).set(util[0])
        tm.gauge("serving/hbm_bw_util",
                 "Per-bucket HBM roofline utilization of the serve "
                 "forward", ("bucket",)).labels(str(bucket)).set(util[1])
    return util


def note_decode(phase, bucket, seconds, rec):
    """Decode-path MFU: ``phase`` is ``prefill`` or ``step``, labeled
    by its prefill/slot bucket; ``rec`` is the owning engine's cost
    record for that program (by reference, like note_serve_batch)."""
    util = _util(rec, seconds)
    if util is None:
        return None
    tm = _tm()
    if tm._enabled:
        tm.gauge("decode/mfu",
                 "Decode-path MFU per program (prefill buckets and "
                 "slot-count step buckets)", ("phase", "bucket")
                 ).labels(phase, str(bucket)).set(util[0])
    return util


def mfu_summary():
    """One-shot roofline summary for diagnostics(): current gauges plus
    the captured-program table."""
    tm = _tm()
    hbm = peak_hbm_bytes_per_s()
    out = {"peak_flops": peak_flops(),
           "peak_hbm_gbps": round(hbm / 1e9, 1) if hbm else None,
           "programs": {}, "unavailable": 0}
    with _costs_lock:
        for (kind, key), rec in sorted(_costs.items()):
            if rec is None:
                out["unavailable"] += 1
                continue
            out["programs"]["%s/%s" % (kind, key)] = {
                "gflops": round(rec["flops"] / 1e9, 3),
                "mbytes": round(rec["bytes"] / 1e6, 3)}
    for metric, field in (("executor/mfu", "executor_mfu"),
                          ("executor/hbm_bw_util", "executor_hbm_bw")):
        fam = tm.REGISTRY._families.get(metric)
        if fam is not None:
            series = fam.series()
            if series:
                out[field] = round(series[0][1].value, 6)
    fam = tm.REGISTRY._families.get("serving/mfu")
    if fam is not None:
        out["serve_bucket_mfu"] = {
            lv[0]: round(c.value, 6) for lv, c in fam.series()}
    return out


# ---------------------------------------------------------------------------
# pillar 2: numerics sentinels (policy side; the in-program side lives
# in Executor._build_fused_step)
# ---------------------------------------------------------------------------

class NumericsError(MXNetError):
    """A numerics sentinel tripped under policy ``raise`` /
    ``checkpoint-and-raise``. Carries the step's ``report`` dict."""

    def __init__(self, msg, report=None):
        super().__init__(msg)
        self.report = report or {}


_MODES = ("off", "step", "full")
_POLICIES = ("warn", "raise", "checkpoint-and-raise")

_numerics_mode = str(_config("MXNET_NUMERICS", "off")).lower()
if _numerics_mode not in _MODES:
    raise MXNetError("MXNET_NUMERICS must be one of %s, got %r"
                     % ("|".join(_MODES), _numerics_mode))
_numerics_policy = str(_config("MXNET_NUMERICS_POLICY", "warn")).lower()
if _numerics_policy not in _POLICIES:
    raise MXNetError("MXNET_NUMERICS_POLICY must be one of %s, got %r"
                     % ("|".join(_POLICIES), _numerics_policy))
_spike_factor = float(_config("MXNET_NUMERICS_SPIKE", 0.0))


def numerics_mode():
    return _numerics_mode


def set_numerics(mode):
    """Set the sentinel mode (also: ``MXNET_NUMERICS``). Returns the
    previous mode. A mode change re-specializes the fused-step program
    (its output signature changes) — flip it between runs, not between
    steps, or eat one recompile."""
    global _numerics_mode
    mode = str(mode).lower()
    if mode not in _MODES:
        raise MXNetError("numerics mode must be one of %s, got %r"
                         % ("|".join(_MODES), mode))
    prev, _numerics_mode = _numerics_mode, mode
    return prev


def numerics_policy():
    return _numerics_policy


def set_numerics_policy(policy):
    """Set the trip policy (also: ``MXNET_NUMERICS_POLICY``). Returns
    the previous policy."""
    global _numerics_policy
    policy = str(policy).lower()
    if policy not in _POLICIES:
        raise MXNetError("numerics policy must be one of %s, got %r"
                         % ("|".join(_POLICIES), policy))
    prev, _numerics_policy = _numerics_policy, policy
    return prev


def set_spike_factor(factor):
    """Grad-norm spike threshold: a step whose global grad norm exceeds
    ``factor``x the running EMA trips the policy. 0 disables spike
    detection (nonfinite detection stays on). Returns the previous
    factor."""
    global _spike_factor
    prev, _spike_factor = _spike_factor, max(0.0, float(factor))
    return prev


def numerics_trips():
    """Total sentinel trips this process (snapshot field)."""
    tm = _tm()
    fam = tm.REGISTRY._families.get("health/numerics_trips_total")
    if fam is None:
        return 0
    return sum(c.value for _lv, c in fam.series())


def check_numerics(report, state=None, where="train_step"):
    """Apply the numerics policy to one step's sentinel ``report``:
    ``{"loss", "grad_norm", "nonfinite", ["per_param"]}`` (host floats,
    read from the fused program's sentinel outputs).

    ``state``: a caller-owned dict (the executor keeps one per bound
    graph) holding the grad-norm EMA for spike detection.

    Healthy steps update the ``health/loss`` / ``health/grad_norm``
    gauges and return None. A trip (nonfinite loss/grads, or a
    grad-norm spike past ``MXNET_NUMERICS_SPIKE`` x EMA) bumps
    ``health/numerics_trips_total``, leaves a flight-recorder record,
    and then applies the policy: ``warn`` logs and training continues;
    ``raise`` / ``checkpoint-and-raise`` raise :class:`NumericsError`
    (``Module.fit`` takes the pre-raise checkpoint for the latter).
    """
    tm = _tm()
    loss = report.get("loss")
    norm = report.get("grad_norm")
    nonfinite = int(report.get("nonfinite", 0) or 0)
    trip = None
    if nonfinite > 0 or (norm is not None and not math.isfinite(norm)):
        trip = "nonfinite"
    elif loss is not None and not math.isfinite(loss):
        trip = "nonfinite_loss"
    elif (_spike_factor > 0 and state is not None and norm is not None):
        ema = state.get("grad_norm_ema")
        if ema is not None and ema > 0 and norm > _spike_factor * ema:
            trip = "grad_spike"
    if tm._enabled:
        if loss is not None and math.isfinite(loss):
            tm.gauge("health/loss",
                     "Loss proxy (mean of the first graph output) from "
                     "the in-program numerics sentinel").set(loss)
        if norm is not None and math.isfinite(norm):
            tm.gauge("health/grad_norm",
                     "Global gradient L2 norm from the in-program "
                     "numerics sentinel").set(norm)
        if nonfinite:
            tm.counter("health/nonfinite_total",
                       "Nonfinite gradient elements seen by the "
                       "numerics sentinel").inc(nonfinite)
    if trip is None:
        if state is not None and norm is not None and math.isfinite(norm):
            ema = state.get("grad_norm_ema")
            state["grad_norm_ema"] = (norm if ema is None
                                      else 0.9 * ema + 0.1 * norm)
        return None

    worst = None
    per_param = report.get("per_param")
    if per_param:
        # blast radius: name the layer. Worst = most nonfinite
        # elements, ties broken by grad norm.
        worst = max(per_param,
                    key=lambda n: (per_param[n].get("nonfinite", 0),
                                   per_param[n].get("norm", 0.0)))
    if tm._enabled:
        tm.counter("health/numerics_trips_total",
                   "Numerics-sentinel trips (nonfinite grads/loss or "
                   "grad-norm spike)", ("kind",)).labels(trip).inc()
    msg = ("numerics sentinel tripped at %s: %s (loss=%s grad_norm=%s "
           "nonfinite=%d%s)"
           % (where, trip, loss, norm, nonfinite,
              "; worst param: %s" % worst if worst else ""))
    try:
        from . import blackbox as _bb
        _bb.record_event("numerics_trip", kind=trip, where=where,
                         loss=loss, grad_norm=norm, nonfinite=nonfinite,
                         worst_param=worst)
    except Exception:
        pass
    try:
        from . import tracing as _trc
        _trc.mark_error(msg)
    except Exception:
        pass
    if _numerics_policy == "warn":
        _log.warning("%s (policy=warn: continuing)", msg)
        return trip
    raise NumericsError(msg, report=report)


# ---------------------------------------------------------------------------
# pillar 3: SLO engine (declarative rules, multi-window burn rate)
# ---------------------------------------------------------------------------

class _HistP99(object):
    """Interval-local p99 (seconds) of a telemetry latency histogram:
    each call returns the p99 of the observations since the PREVIOUS
    call (linear interpolation inside the winning bucket), or None
    when nothing new was observed — no traffic is not a violation."""

    def __init__(self, metric):
        self._metric = metric
        self._prev = {}

    def __call__(self):
        tm = _tm()
        fam = tm.REGISTRY._families.get(self._metric)
        if fam is None or fam.kind != "histogram":
            return None
        # merge every labeled series into one distribution
        bounds, merged = None, None
        for lv, child in fam.series():
            counts = child.bucket_counts()          # cumulative
            if merged is None:
                bounds = list(child.buckets) + [float("inf")]
                merged = [0] * len(counts)
            for i, c in enumerate(counts):
                merged[i] += c
        if merged is None:
            return None
        prev = self._prev.get("counts")
        self._prev["counts"] = merged
        if prev is None or len(prev) != len(merged):
            return None
        delta = [b - a for a, b in zip(prev, merged)]
        total = delta[-1]
        if total <= 0:
            return None
        target = 0.99 * total
        lo = 0.0
        for i, cum in enumerate(delta):
            if cum >= target:
                hi = bounds[i]
                if hi == float("inf"):
                    return lo if lo > 0 else bounds[-2]
                prev_cum = delta[i - 1] if i else 0
                in_bucket = delta[i] - prev_cum
                frac = ((target - prev_cum) / in_bucket) if in_bucket \
                    else 1.0
                return lo + (hi - lo) * frac
            lo = bounds[i]
        return bounds[-2]


class _CounterDelta(object):
    """Events since the previous evaluation of a counter family
    (summed over labels); None before the first sample."""

    def __init__(self, metric):
        self._metric = metric
        self._prev = None

    def __call__(self):
        tm = _tm()
        fam = tm.REGISTRY._families.get(self._metric)
        total = (sum(c.value for _lv, c in fam.series())
                 if fam is not None else 0)
        prev, self._prev = self._prev, total
        if prev is None:
            return None
        return total - prev


class _GaugeValue(object):
    """Current value of a gauge family (max over labels); None when
    the gauge was never set."""

    def __init__(self, metric):
        self._metric = metric

    def __call__(self):
        tm = _tm()
        fam = tm.REGISTRY._families.get(self._metric)
        if fam is None:
            return None
        vals = [c.value for _lv, c in fam.series()]
        return max(vals) if vals else None


class _Rule(object):
    __slots__ = ("name", "value_fn", "threshold", "cmp", "short_s",
                 "long_s", "burn", "mode", "description", "samples",
                 "state", "since", "last_value", "lock")

    def __init__(self, name, value_fn, threshold, cmp, short_s, long_s,
                 burn, description, mode="burn"):
        self.name = name
        self.value_fn = value_fn
        self.threshold = float(threshold)
        self.cmp = cmp
        self.short_s = float(short_s)
        self.long_s = float(long_s)
        self.burn = float(burn)
        self.mode = mode                 # "burn" | "events"
        self.description = description
        self.samples = deque()           # (t, violating)
        self.state = "ok"
        self.since = _monotonic()
        self.last_value = None
        self.lock = threading.Lock()

    def _violating(self, value):
        if value is None:
            return False
        return value > self.threshold if self.cmp == ">" \
            else value < self.threshold

    def _window_frac(self, now, window):
        pts = [v for (t, v) in self.samples if now - t <= window]
        if not pts:
            return 0.0, 0
        return sum(pts) / float(len(pts)), len(pts)

    def evaluate(self, now):
        """One evaluator tick: sample, slide windows, maybe
        transition. Returns ('ok'|'firing', transitioned?)."""
        try:
            value = self.value_fn()
        except Exception:
            value = None
        with self.lock:
            self.last_value = value
            self.samples.append((now, 1 if self._violating(value) else 0))
            while self.samples and now - self.samples[0][0] > self.long_s:
                self.samples.popleft()
            short_frac, n_short = self._window_frac(now, self.short_s)
            long_frac, n_long = self._window_frac(now, self.long_s)
            prev = self.state
            if self.mode == "events":
                # discrete-event rules (counter deltas): ONE event is
                # already the signal — a numerics trip or a kvstore
                # giveup must page immediately, and burn-fraction math
                # would drown a single event among quiet ticks. Fires
                # on any violating sample in the short window, clears
                # when the window has drained.
                violated = any(v for (t, v) in self.samples
                               if now - t <= self.short_s)
                self.state = "firing" if violated else "ok"
            elif prev == "ok":
                # continuous signals: multi-window burn rate — both
                # the fast and the slow window must burn, so a
                # one-sample blip cannot page and a sustained
                # regression cannot hide behind an old quiet period
                if (n_short >= 2 and n_long >= 2
                        and short_frac >= self.burn
                        and long_frac >= self.burn):
                    self.state = "firing"
            else:
                if short_frac < self.burn:
                    self.state = "ok"
            transitioned = self.state != prev
            if transitioned:
                self.since = now
            return self.state, transitioned

    def snapshot(self, now):
        with self.lock:
            short_frac, _ = self._window_frac(now, self.short_s)
            long_frac, _ = self._window_frac(now, self.long_s)
            return {"name": self.name, "state": self.state,
                    "value": (round(self.last_value, 6)
                              if isinstance(self.last_value, float)
                              else self.last_value),
                    "threshold": self.threshold, "cmp": self.cmp,
                    "burn": self.burn, "mode": self.mode,
                    "short_window_s": self.short_s,
                    "long_window_s": self.long_s,
                    "short_burn_frac": round(short_frac, 3),
                    "long_burn_frac": round(long_frac, 3),
                    "since_s": round(now - self.since, 1),
                    "description": self.description}


_rules = {}
_rules_lock = threading.Lock()
_defaults_installed = False
_interval = float(_config("MXNET_SLO_INTERVAL_S", 2.0))
_evaluator = None
_evaluator_stop = threading.Event()


def watch(name, value_fn=None, threshold=0.0, cmp=">", short_s=30.0,
          long_s=120.0, burn=0.5, description="", histogram_p99=None,
          counter_delta=None, gauge=None, mode=None):
    """Register (or replace) one SLO rule.

    Exactly one source: ``value_fn`` (any callable returning a float
    or None — None samples never violate), ``histogram_p99=<metric>``
    (interval-local p99 seconds of a latency histogram),
    ``counter_delta=<metric>`` (events since the previous evaluation),
    or ``gauge=<metric>`` (current value, max over labels).

    Two firing modes. ``burn`` (default for continuous sources): fires
    when the fraction of violating samples is >= ``burn`` over BOTH
    the ``short_s`` and ``long_s`` windows, clears when the short
    window drops below ``burn``. ``events`` (default for
    ``counter_delta`` sources): a single violating sample fires
    immediately and the rule stays firing until the short window
    drains — a numerics trip or a kvstore giveup is the signal all by
    itself, and burn-fraction math would drown one event among quiet
    evaluator ticks. Transitions land in
    ``health/alert_transitions_total``, the flight recorder, and a
    ``health.alert`` root span.
    """
    sources = [s for s in (value_fn, histogram_p99, counter_delta, gauge)
               if s is not None]
    if len(sources) != 1:
        raise MXNetError("watch(%r) needs exactly one of value_fn / "
                         "histogram_p99 / counter_delta / gauge" % name)
    # defaults install first so an explicit watch() always WINS over
    # the default rule of the same name (re-watch = replace)
    _ensure_defaults()
    if mode is None:
        mode = "events" if counter_delta is not None else "burn"
    if mode not in ("burn", "events"):
        raise MXNetError("watch(%r): mode must be 'burn' or 'events'"
                         % name)
    if histogram_p99 is not None:
        value_fn = _HistP99(histogram_p99)
    elif counter_delta is not None:
        value_fn = _CounterDelta(counter_delta)
    elif gauge is not None:
        value_fn = _GaugeValue(gauge)
    rule = _Rule(name, value_fn, threshold, cmp, short_s, long_s, burn,
                 description, mode=mode)
    with _rules_lock:
        _rules[name] = rule
    ensure_evaluator()
    return rule


def unwatch(name):
    """Remove one rule; True when it existed."""
    with _rules_lock:
        return _rules.pop(name, None) is not None


def rules():
    """Names of the registered rules."""
    _ensure_defaults()
    with _rules_lock:
        return sorted(_rules)


def _ensure_defaults():
    """Install the default rule set once (idempotent, lazy — nothing
    starts until someone watches, serves /alerts, or evaluates)."""
    global _defaults_installed
    if _defaults_installed:
        return
    _defaults_installed = True
    serve_ms = float(_config("MXNET_SLO_SERVE_P99_MS", 1000.0))
    itl_ms = float(_config("MXNET_SLO_DECODE_ITL_P99_MS", 250.0))
    qd = 0.9 * float(_config("MXNET_SERVE_QUEUE_DEPTH", 64))
    watch("serve_p99", histogram_p99="serving/request_seconds",
          threshold=serve_ms / 1e3,
          description="serve request p99 (enqueue->result) over "
                      "MXNET_SLO_SERVE_P99_MS")
    watch("decode_itl_p99", histogram_p99="decode/step_seconds",
          threshold=itl_ms / 1e3,
          description="decode inter-token latency p99 (step wall) over "
                      "MXNET_SLO_DECODE_ITL_P99_MS")
    watch("queue_depth", gauge="serving/queue_depth", threshold=qd,
          description="serve queue persistently above 90% of "
                      "MXNET_SERVE_QUEUE_DEPTH (admission rejections "
                      "imminent)")
    watch("worker_restart_burn",
          counter_delta="serving/worker_restarts_total",
          threshold=0.0,
          description="serve/decode worker crash-restarts burning the "
                      "restart budget")
    watch("kv_giveups", counter_delta="kvstore/giveups_total",
          threshold=0.0,
          description="kvstore ops abandoned after exhausting retries "
                      "(parameter server unreachable)")
    watch("numerics", counter_delta="health/numerics_trips_total",
          threshold=0.0,
          description="numerics-sentinel trips (nonfinite grads/loss "
                      "or grad-norm spike)")
    watch("badput_fraction", gauge="goodput/badput_fraction",
          threshold=float(_config("MXNET_SLO_BADPUT_FRACTION", 0.5)),
          description="goodput ledger: fraction of run wall NOT spent "
                      "in useful training-step compute sustained above "
                      "MXNET_SLO_BADPUT_FRACTION (compiles, data "
                      "waits, rescales, restarts, idle)")


def set_interval(seconds):
    """Evaluator wake period (also: MXNET_SLO_INTERVAL_S). Returns the
    previous period; takes effect on the next tick."""
    global _interval
    prev, _interval = _interval, max(0.01, float(seconds))
    return prev


def _transition(rule, state, now):
    tm = _tm()
    if tm._enabled:
        tm.counter("health/alert_transitions_total",
                   "SLO rule state transitions", ("rule", "state")
                   ).labels(rule.name, state).inc()
    try:
        from . import blackbox as _bb
        _bb.record_event("alert", rule=rule.name, state=state,
                         value=rule.last_value, threshold=rule.threshold)
    except Exception:
        pass
    try:
        from . import tracing as _trc
        with _trc.start_span("health.alert",
                             attrs={"rule": rule.name, "state": state,
                                    "value": rule.last_value,
                                    "threshold": rule.threshold}):
            pass
    except Exception:
        pass
    (_log.warning if state == "firing" else _log.info)(
        "SLO rule %r -> %s (value=%s threshold=%s)",
        rule.name, state, rule.last_value, rule.threshold)


def evaluate_once(now=None):
    """One evaluator pass over every rule (the background thread's
    body; callable directly in tests). Returns the firing rule
    names."""
    _ensure_defaults()
    now = _monotonic() if now is None else now
    with _rules_lock:
        current = list(_rules.values())
    firing = []
    for rule in current:
        state, transitioned = rule.evaluate(now)
        if transitioned:
            _transition(rule, state, now)
        if state == "firing":
            firing.append(rule.name)
    return firing


def _evaluator_main():
    while not _evaluator_stop.wait(_interval):
        try:
            evaluate_once()
        except Exception:
            _log.exception("SLO evaluator pass failed")


def ensure_evaluator():
    """Start the background evaluator thread once (daemon; stops with
    the process or via :func:`stop_evaluator`)."""
    global _evaluator
    _ensure_defaults()
    if _evaluator is not None and _evaluator.is_alive():
        return _evaluator
    with _rules_lock:
        if _evaluator is not None and _evaluator.is_alive():
            return _evaluator
        _evaluator_stop.clear()
        t = threading.Thread(target=_evaluator_main,
                             name="mxnet-slo-evaluator", daemon=True)
        t.start()
        _evaluator = t
    return _evaluator


def stop_evaluator(timeout=5.0):
    """Stop the evaluator thread (test isolation)."""
    global _evaluator
    _evaluator_stop.set()
    t = _evaluator
    if t is not None and t.is_alive():
        t.join(timeout=timeout)
    _evaluator = None


def alerts_firing():
    """Names of the rules currently firing (snapshot field; does not
    start the evaluator)."""
    with _rules_lock:
        return sorted(r.name for r in _rules.values()
                      if r.state == "firing")


def alerts_payload():
    """JSON-ready payload for ``/alerts``: every rule's state, value,
    windows, and burn fractions, newest transitions first."""
    ensure_evaluator()                   # hitting the endpoint arms it
    now = _monotonic()
    with _rules_lock:
        rows = [r.snapshot(now) for r in _rules.values()]
    rows.sort(key=lambda r: (r["state"] != "firing", r["name"]))
    return {"rules": rows,
            "firing": [r["name"] for r in rows if r["state"] == "firing"],
            "interval_s": _interval,
            "evaluator_alive": (_evaluator is not None
                                and _evaluator.is_alive())}


def alerts_endpoint(query=""):
    """(status_code, payload) for ``GET /alerts`` — the one
    implementation behind both mounts (telemetry.serve and
    serve.serve_http), the traces_endpoint pattern.

    ``?format=json`` returns the *machine contract* the fleet
    autoscaler polls: a trimmed, stability-guaranteed view of each
    rule (name, state, mode, value/threshold, windows + burn
    fractions) keyed under ``format: "json"``. The default (human)
    payload — the full snapshots with descriptions, ordering, and
    evaluator status — is unchanged, so dashboards keep rendering
    exactly what they always did while control loops get fields that
    won't move under them."""
    import urllib.parse
    params = urllib.parse.parse_qs(query or "")
    fmt = (params.get("format") or [""])[0]
    payload = alerts_payload()
    if fmt != "json":
        return 200, payload
    rules = [{"rule": r["name"], "state": r["state"], "mode": r["mode"],
              "value": r["value"], "threshold": r["threshold"],
              "cmp": r["cmp"], "since_s": r["since_s"],
              "windows": [
                  {"window_s": r["short_window_s"],
                   "burn_frac": r["short_burn_frac"]},
                  {"window_s": r["long_window_s"],
                   "burn_frac": r["long_burn_frac"]}],
              "burn_threshold": r["burn"]} for r in payload["rules"]]
    return 200, {"format": "json", "firing": payload["firing"],
                 "interval_s": payload["interval_s"], "rules": rules}


def reset():
    """Test isolation: stop the evaluator, drop rules and captured
    program costs, re-install defaults lazily on next use."""
    global _defaults_installed
    stop_evaluator()
    with _rules_lock:
        _rules.clear()
    _defaults_installed = False
    with _costs_lock:
        _costs.clear()
