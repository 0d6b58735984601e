"""Always-on runtime telemetry: metrics registry + sinks.

The profiler (profiler.py) answers "what happened during this traced
window"; this module answers "what is the process doing right now" — the
always-on, low-overhead counters/gauges/histograms a serving deployment
scrapes. Reference analogs: the engine profiler's aggregate tables
(src/profiler/aggregate_stats.cc) and the storage profiler
(src/profiler/storage_profiler.h), generalized into one registry that
every layer reports through.

Three sinks:

1. :func:`render_prometheus` — Prometheus text exposition format;
2. :func:`serve` — a stdlib-only HTTP server mounting ``/metrics`` and
   ``/healthz`` (what an inference ``Predictor`` starts for scraping);
3. a bridge mirroring selected gauges into the profiler's chrome trace
   as ``ph:"C"`` counter events (:func:`bridge_to_profiler`), so traces
   and scraped metrics tell one consistent story.

Naming scheme: instruments use short path-style names
(``op/dispatch_seconds``, ``hbm/bytes_in_use``); rendering prefixes
``mxnet_`` and maps every non-metric character to ``_``
(``mxnet_op_dispatch_seconds``). Labels are free-form key/value pairs
(``{op="dot"}``, ``{device="TPU_0"}``).

Cost model: one module-bool check when disabled (MXNET_TELEMETRY=0);
when enabled, an op dispatch pays two ``perf_counter`` reads, one dict
lookup, and three locked integer bumps — structured to stay within a few
percent of the uninstrumented dispatch (asserted by
tests/test_telemetry.py::test_dispatch_overhead). Unobserved metrics
cost nothing: labeled children materialize on first observation.

JIT-compile tracking hooks ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` events — the same feed
XLA's own dashboards use — so compile count/time covers *every* compile
(eager op cache misses, executor graph builds, CachedOp modes) without
touching the compile path itself.
"""
from __future__ import annotations

import bisect
import json
import threading
import time

__all__ = ["Registry", "Counter", "Gauge", "Histogram", "REGISTRY",
           "counter", "gauge", "histogram", "enable", "enabled",
           "render_prometheus", "serve", "TelemetryServer",
           "bridge_to_profiler", "snapshot", "diagnostics", "reset",
           "exemplars", "DEFAULT_LATENCY_BUCKETS"]

# Fixed log-scale latency buckets (seconds): 1-2.5-5 per decade from
# 10us to 10s — op dispatch sits in the left decades, XLA compiles and
# batch waits in the right ones.
DEFAULT_LATENCY_BUCKETS = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

monotonic = time.perf_counter

# a histogram's worst-case exemplar decays after this long, so "worst
# recent" tracks the current regime rather than a cold-start outlier
EXEMPLAR_WINDOW_S = 300.0


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

class Counter(object):
    """Monotonically increasing count."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, amount=1):
        with self._lock:
            self._value += amount

    @property
    def value(self):
        return self._value


class Gauge(object):
    """Point-in-time value. ``set`` mirrors into the profiler trace as a
    ``ph:"C"`` counter event when this gauge's family is bridged and the
    profiler is running."""

    __slots__ = ("_value", "_lock", "_bridge_name")

    def __init__(self, bridge_name=None):
        self._value = 0.0
        self._lock = threading.Lock()
        self._bridge_name = bridge_name

    def set(self, value):
        value = float(value)
        with self._lock:
            self._value = value
        if self._bridge_name is not None:
            from . import profiler
            if profiler.is_running():
                profiler.record_counter(self._bridge_name, value)

    def inc(self, amount=1):
        with self._lock:
            self._value += amount

    def dec(self, amount=1):
        self.inc(-amount)

    @property
    def value(self):
        return self._value


class Histogram(object):
    """Cumulative histogram over fixed upper bounds (+Inf implicit).

    ``observe(value, trace_id=...)`` additionally keeps a worst-recent
    exemplar — the trace id of the largest observation in the last
    ``EXEMPLAR_WINDOW_S`` seconds — so a /metrics p99 links to a
    concrete /traces timeline."""

    __slots__ = ("buckets", "_counts", "_sum", "_count", "_lock",
                 "_worst_v", "_worst_id", "_worst_t")

    def __init__(self, buckets=DEFAULT_LATENCY_BUCKETS):
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()
        self._worst_v = None
        self._worst_id = None
        self._worst_t = 0.0

    def observe(self, value, trace_id=None):
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1
            if trace_id is not None:
                now = monotonic()
                if (self._worst_v is None or value >= self._worst_v
                        or now - self._worst_t > EXEMPLAR_WINDOW_S):
                    self._worst_v = value
                    self._worst_id = trace_id
                    self._worst_t = now

    def exemplar(self):
        """(value, trace_id, age_seconds) of the worst recent traced
        observation, or None when nothing traced was observed within
        the decay window — a frozen exemplar from before traffic went
        idle (or sampling was turned off) would point an operator at a
        long-evicted timeline presented as current."""
        with self._lock:
            if self._worst_id is None:
                return None
            age = monotonic() - self._worst_t
            if age > EXEMPLAR_WINDOW_S:
                self._worst_v = None
                self._worst_id = None
                self._worst_t = 0.0
                return None
            return (self._worst_v, self._worst_id, age)

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def bucket_counts(self):
        """Cumulative counts per upper bound, ending with +Inf."""
        out, acc = [], 0
        with self._lock:
            raw = list(self._counts)
        for c in raw:
            acc += c
            out.append(acc)
        return out


class Family(object):
    """One named metric: an instrument per label-value combination.
    Unlabeled metrics hold a single default child."""

    __slots__ = ("name", "kind", "help", "labelnames", "buckets",
                 "_children", "_lock", "_bridged")

    def __init__(self, name, kind, help="", labelnames=(), buckets=None):
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = buckets
        self._children = {}
        self._lock = threading.Lock()
        self._bridged = False

    def _label_suffix(self, labelvalues):
        """``{name=value,...}`` series-key suffix ("" when unlabeled) —
        the one spelling shared by snapshot(), exemplars() and the
        chrome-trace bridge."""
        if not labelvalues:
            return ""
        return "{%s}" % ",".join(
            "%s=%s" % kv for kv in zip(self.labelnames, labelvalues))

    def _bridge_name_for(self, labelvalues):
        """Chrome-trace counter name for a bridged gauge child (None
        when this family is not bridged)."""
        if not self._bridged:
            return None
        return prom_name(self.name) + self._label_suffix(labelvalues)

    def _make(self, labelvalues):
        if self.kind == "counter":
            return Counter()
        if self.kind == "gauge":
            return Gauge(self._bridge_name_for(labelvalues))
        return Histogram(self.buckets or DEFAULT_LATENCY_BUCKETS)

    def labels(self, *labelvalues, **labelkw):
        if labelkw:
            labelvalues = tuple(str(labelkw[n]) for n in self.labelnames)
        else:
            labelvalues = tuple(str(v) for v in labelvalues)
        if len(labelvalues) != len(self.labelnames):
            raise ValueError("metric %r expects labels %s"
                             % (self.name, list(self.labelnames)))
        child = self._children.get(labelvalues)
        if child is None:
            with self._lock:
                child = self._children.get(labelvalues)
                if child is None:
                    child = self._make(labelvalues)
                    self._children[labelvalues] = child
        return child

    # unlabeled convenience: family proxies its single default child
    def _default(self):
        return self.labels()

    def inc(self, amount=1):
        self._default().inc(amount)

    def set(self, value):
        self._default().set(value)

    def dec(self, amount=1):
        self._default().dec(amount)

    def observe(self, value, trace_id=None):
        self._default().observe(value, trace_id=trace_id)

    @property
    def value(self):
        return self._default().value

    def series(self):
        """Snapshot [(labelvalues, child)] observed so far."""
        with self._lock:
            return list(self._children.items())


class Registry(object):
    """Thread-safe get-or-create store of metric families."""

    def __init__(self):
        self._families = {}
        self._lock = threading.Lock()

    def _get_or_create(self, name, kind, help, labelnames, buckets=None):
        fam = self._families.get(name)
        if fam is not None:
            if fam.kind != kind:
                raise ValueError("metric %r already registered as %s"
                                 % (name, fam.kind))
            return fam
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = Family(name, kind, help, labelnames, buckets)
                self._families[name] = fam
        return fam

    def counter(self, name, help="", labelnames=()):
        return self._get_or_create(name, "counter", help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._get_or_create(name, "gauge", help, labelnames)

    def histogram(self, name, help="", labelnames=(), buckets=None):
        return self._get_or_create(name, "histogram", help, labelnames,
                                   buckets)

    def families(self):
        with self._lock:
            return [self._families[k] for k in sorted(self._families)]

    def reset(self):
        with self._lock:
            self._families.clear()

    def render_prometheus(self):
        """Prometheus text exposition format (version 0.0.4)."""
        lines = []
        for fam in self.families():
            series = fam.series()
            if not series:
                continue
            pname = prom_name(fam.name)
            if fam.help:
                lines.append("# HELP %s %s"
                             % (pname, fam.help.replace("\n", " ")))
            lines.append("# TYPE %s %s" % (pname, fam.kind))
            for labelvalues, child in sorted(series):
                base_labels = list(zip(fam.labelnames, labelvalues))
                if fam.kind in ("counter", "gauge"):
                    lines.append("%s%s %s" % (pname, _label_str(base_labels),
                                              _fmt(child.value)))
                else:
                    bounds = list(child.buckets) + [float("inf")]
                    for ub, c in zip(bounds, child.bucket_counts()):
                        lines.append("%s_bucket%s %d" % (
                            pname,
                            _label_str(base_labels + [("le", _le(ub))]), c))
                    lines.append("%s_sum%s %s"
                                 % (pname, _label_str(base_labels),
                                    _fmt(child.sum)))
                    lines.append("%s_count%s %d"
                                 % (pname, _label_str(base_labels),
                                    child.count))
        return "\n".join(lines) + "\n"

    def snapshot(self):
        """Flat dict of every observed series (for JSON embedding)."""
        out = {}
        for fam in self.families():
            for labelvalues, child in fam.series():
                key = fam.name + fam._label_suffix(labelvalues)
                if fam.kind == "histogram":
                    out[key] = {"count": child.count,
                                "sum": round(child.sum, 6)}
                else:
                    v = child.value
                    out[key] = round(v, 6) if isinstance(v, float) else v
        return out


def prom_name(name):
    clean = "".join(c if (c.isalnum() or c in "_:") else "_" for c in name)
    if not clean.startswith("mxnet_"):
        clean = "mxnet_" + clean
    return clean


def _label_str(pairs):
    if not pairs:
        return ""
    return "{%s}" % ",".join(
        '%s="%s"' % (k, str(v).replace("\\", "\\\\").replace('"', '\\"'))
        for k, v in pairs)


def _le(ub):
    return "+Inf" if ub == float("inf") else repr(ub)


def _fmt(v):
    if isinstance(v, float) and not v.is_integer():
        return repr(v)
    return str(int(v))


# ---------------------------------------------------------------------------
# default registry + enable switch
# ---------------------------------------------------------------------------

REGISTRY = Registry()


def counter(name, help="", labelnames=()):
    return REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()):
    fam = REGISTRY.gauge(name, help, labelnames)
    if name in _BRIDGED_GAUGES:
        fam._bridged = True
    return fam


def histogram(name, help="", labelnames=(), buckets=None):
    return REGISTRY.histogram(name, help, labelnames, buckets)


def render_prometheus():
    return REGISTRY.render_prometheus()


def _config_enabled():
    try:
        from .config import get
        return bool(get("MXNET_TELEMETRY"))
    except Exception:
        return True


_enabled = _config_enabled()


def enabled():
    return _enabled


def enable(on=True):
    """Turn hot-path instrumentation on/off (also: MXNET_TELEMETRY=0).
    Returns the previous state. Registry contents are preserved."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    if _enabled:
        _ensure_compile_listener()
    return prev


def reset():
    """Clear every collected series AND the compile totals (test
    isolation) so snapshot() and the rendered families stay in
    agreement. Instrument handles cached by hot paths are re-resolved
    on next use."""
    global _compile_count, _compile_time, _disk_hits
    REGISTRY.reset()
    _op_cache.clear()
    _kv_cache.clear()
    del _hitmiss[:]
    with _compile_lock:
        _compile_count = 0
        _compile_time = 0.0
        _disk_hits = 0


# ---------------------------------------------------------------------------
# profiler bridge
# ---------------------------------------------------------------------------

# gauges mirrored into the profiler chrome trace as ph:"C" counter
# events while the profiler runs (record_counter is gated on
# profiler.is_running, so the bridge is free when no trace is active)
_BRIDGED_GAUGES = {"hbm/bytes_in_use", "hbm/peak_bytes",
                   "io/queue_depth", "training/throughput"}


def bridge_to_profiler(names=("hbm/bytes_in_use", "hbm/peak_bytes",
                              "io/queue_depth", "training/throughput")):
    """Select which gauge families mirror into the profiler trace.
    Pass an empty tuple to disconnect the bridge entirely."""
    _BRIDGED_GAUGES.clear()
    _BRIDGED_GAUGES.update(names or ())
    for fam in REGISTRY.families():
        if fam.kind == "gauge":
            fam._bridged = fam.name in _BRIDGED_GAUGES
            # rebind live children in place — their current values must
            # survive (a scrape between rebind and next observation
            # would otherwise see the series vanish)
            with fam._lock:
                for labelvalues, child in fam._children.items():
                    child._bridge_name = fam._bridge_name_for(labelvalues)


# ---------------------------------------------------------------------------
# jit-compile tracking (jax.monitoring feed)
# ---------------------------------------------------------------------------

_compile_count = 0          # bumped by the jax.monitoring listener
_compile_time = 0.0
_disk_hits = 0              # compile requests served from the persistent
                            # compilation cache on disk (programs.py)
_compile_lock = threading.Lock()    # compiles fire on whichever thread
_listener_on = False
_listener_lock = threading.Lock()

# persistent-cache attribution: jax fires the plain
# /jax/compilation_cache/cache_hits event INSIDE compile_or_get_cached,
# before the wrapping backend_compile_duration event is recorded at
# context exit — both on the compiling thread. A thread-local flag set
# by the plain event and consumed by the duration event pairs them, so
# the compile-vs-disk-hit split never cross-counts between threads.
_tls_hit = threading.local()

# per-thread cumulative (compile_requests, disk_hits): lets
# programs.get_or_build attribute exactly ITS build's compiles even
# while another thread compiles something unrelated
_tls_counts = threading.local()

# health.capture_cost runs XLA's HLO cost pass, which emits pseudo
# compile events of its own; counting those would poison every
# zero-recompile assertion the serving/training tests bank. The pass
# runs synchronously on the capturing thread, so a thread-local flag
# fences exactly its events.
_suppress = threading.local()


class _SuppressCompileTracking(object):
    __slots__ = ()

    def __enter__(self):
        _suppress.on = getattr(_suppress, "on", 0) + 1
        return self

    def __exit__(self, *exc):
        _suppress.on -= 1
        return False


def suppress_compile_tracking():
    """Context manager: ignore backend-compile events fired on this
    thread (used by health.capture_cost around the HLO cost pass)."""
    return _SuppressCompileTracking()


def _on_jax_event(name, secs, **_kw):
    if name.endswith("backend_compile_duration"):
        if getattr(_suppress, "on", 0):
            return
        # with the persistent compile cache on, this event fires for
        # BOTH a real backend compile and a disk load (jax wraps
        # compile_or_get_cached) — which is exactly the honest "a trace
        # reached the compiler" signal the zero-recompile assertions
        # bank. The disk-hit flag (set by the plain cache_hits event
        # just before, same thread) splits the two for the
        # programs/compile_total vs programs/disk_hits_total counters.
        disk_hit = getattr(_tls_hit, "on", False)
        _tls_hit.on = False
        global _compile_count, _compile_time, _disk_hits
        with _compile_lock:
            _compile_count += 1
            _compile_time += secs
            if disk_hit:
                _disk_hits += 1
        _tls_counts.compiles = getattr(_tls_counts, "compiles", 0) + 1
        if disk_hit:
            _tls_counts.disk = getattr(_tls_counts, "disk", 0) + 1
        counter("jit/backend_compile_total",
                "XLA compile requests, all layers (real backend "
                "compiles AND persistent-cache disk loads: every "
                "trace that reached the compiler)").inc()
        if disk_hit:
            counter("programs/disk_hits_total",
                    "Compile requests served from the persistent "
                    "compilation cache on disk "
                    "(programs.cache_dir())").inc()
        else:
            counter("programs/compile_total",
                    "Real XLA backend compiles (persistent-cache "
                    "misses + uncached compiles)").inc()
        try:
            # every backend compile is a lifecycle event: a mid-traffic
            # recompile found in a post-mortem ring names the regression
            from . import blackbox as _bb
            if _bb._enabled:
                _bb.record_event("compile", seconds=round(secs, 4),
                                 disk_hit=disk_hit)
        except Exception:
            pass
        hist = histogram("jit/backend_compile_seconds",
                         "XLA backend compile latency")
        try:
            # the listener fires on the compiling thread, so the active
            # trace context (if any) is the dispatch that triggered the
            # compile: attribute the compile to that timeline
            from . import tracing as _tr
            ctx = _tr.active()
            if ctx is not None:
                now = monotonic()
                _tr.record_span("executor.compile", ctx, now - secs, now,
                                {"seconds": round(secs, 4)})
                hist.observe(secs, trace_id=ctx.trace_id)
                return
        except Exception:
            pass
        hist.observe(secs)


def _on_jax_plain_event(name, **_kw):
    """Plain (non-duration) jax.monitoring events: a persistent-cache
    disk hit announces itself here before the wrapping
    backend_compile_duration event lands on the same thread."""
    if name.endswith("compilation_cache/cache_hits"):
        if getattr(_suppress, "on", 0):
            return
        _tls_hit.on = True


_listener_dead = False      # jax.monitoring unavailable: stop retrying


def _ensure_compile_listener():
    """Install the jax.monitoring compile listeners once. A failed
    import is cached (this sits behind the hot dispatch path — it must
    not retry the import machinery per op)."""
    global _listener_on, _listener_dead
    if _listener_on:
        return True
    if _listener_dead:
        return False
    with _listener_lock:
        if _listener_on:
            return True
        if _listener_dead:
            return False
        try:
            import jax.monitoring as _jm
        except Exception:
            _listener_dead = True
            return False
        _jm.register_event_duration_secs_listener(_on_jax_event)
        try:
            _jm.register_event_listener(_on_jax_plain_event)
        except Exception:
            pass                 # no plain-event feed: no disk-hit split
        _listener_on = True
    return True


def compile_count():
    return _compile_count


def compile_time():
    return _compile_time


def disk_hit_count():
    """Compile requests served from the persistent compilation cache
    on disk (a subset of :func:`compile_count`)."""
    return _disk_hits


def thread_compile_stats():
    """(compile_requests, disk_hits) observed on THIS thread — the
    attribution programs.get_or_build brackets a build with, immune to
    concurrent compiles on other threads. With MXNET_TELEMETRY=0 the
    listener is never installed from here (the off switch must keep
    every jit site quiet even though they all route through
    programs.get_or_build) and the stats stay (0, 0)."""
    if _enabled and not _listener_on:
        _ensure_compile_listener()
    return (getattr(_tls_counts, "compiles", 0),
            getattr(_tls_counts, "disk", 0))


# ---------------------------------------------------------------------------
# hot-path helpers (tiny call sites, children cached here)
# ---------------------------------------------------------------------------

_op_cache = {}    # op name -> (dispatch Counter, latency Histogram)
_kv_cache = {}    # kvstore op -> (Counter, Histogram, bytes Counter)
_hitmiss = []     # [hit Counter, miss Counter] resolved on first dispatch


def dispatch_begin():
    """Start-of-dispatch token for invoke_op: (t0, compile_count)."""
    if not _listener_on:
        _ensure_compile_listener()
    return (monotonic(), _compile_count)


def dispatch_end(name, token):
    """Record one op dispatch: count, latency, jit-cache hit/miss."""
    dt = monotonic() - token[0]
    pair = _op_cache.get(name)
    if pair is None:
        pair = (counter("op/dispatch_total", "Op dispatches",
                        ("op",)).labels(name),
                histogram("op/dispatch_seconds", "Op dispatch latency "
                          "(host-side, async submit)", ("op",)).labels(name))
        _op_cache[name] = pair
    pair[0].inc()
    pair[1].observe(dt)
    if not _hitmiss:
        _hitmiss[:] = [
            counter("jit/cache_hits_total",
                    "Op dispatches served from the jit cache")._default(),
            counter("jit/cache_misses_total",
                    "Op dispatches that triggered an XLA compile"
                    )._default()]
    _hitmiss[_compile_count > token[1]].inc()


def record_kvstore(op, dt, nbytes, trace_id=None):
    trip = _kv_cache.get(op)
    if trip is None:
        trip = (counter("kvstore/ops_total", "KVStore calls",
                        ("op",)).labels(op),
                histogram("kvstore/seconds", "KVStore call latency",
                          ("op",)).labels(op),
                counter("kvstore/bytes_total", "Bytes moved through the "
                        "KVStore", ("op",)).labels(op))
        _kv_cache[op] = trip
    trip[0].inc()
    if dt is not None:
        trip[1].observe(dt, trace_id=trace_id)
    if nbytes:
        trip[2].inc(int(nbytes))


def exemplars():
    """Worst-recent trace exemplars of every latency histogram:
    {"name{labels}": {"seconds", "trace_id", "age_s"}}. Rendered by the
    /traces endpoint so a scraped p99 links to a concrete timeline (the
    0.0.4 text format has no exemplar syntax, so they ride here)."""
    out = {}
    for fam in REGISTRY.families():
        if fam.kind != "histogram":
            continue
        for labelvalues, child in fam.series():
            ex = child.exemplar()
            if ex is None:
                continue
            key = fam.name + fam._label_suffix(labelvalues)
            out[key] = {"seconds": round(ex[0], 6), "trace_id": ex[1],
                        "age_s": round(ex[2], 1)}
    return out


def record_hbm(device, bytes_in_use, peak_bytes=None):
    dev = str(device)
    gauge("hbm/bytes_in_use", "Device memory currently allocated",
          ("device",)).labels(dev).set(bytes_in_use)
    if peak_bytes is not None:
        gauge("hbm/peak_bytes", "Peak device memory allocated",
              ("device",)).labels(dev).set(peak_bytes)


# ---------------------------------------------------------------------------
# /metrics HTTP server (stdlib only)
# ---------------------------------------------------------------------------

# last-started metrics endpoint of this process ("host:port"), set by
# serve() / serve.serve_http and published in the elastic heartbeat so
# the cluster observatory can discover this rank with no extra config
_server_endpoint = None


def server_endpoint():
    """``"host:port"`` of this process's most recently started metrics
    mount (telemetry.serve or serve.serve_http), or None."""
    return _server_endpoint


def set_server_endpoint(host, port):
    global _server_endpoint
    _server_endpoint = "%s:%d" % (host, int(port)) if port else None


class TelemetryServer(object):
    """Handle on a running metrics endpoint (returned by :func:`serve`)."""

    def __init__(self, httpd, thread):
        self._httpd = httpd
        self._thread = thread
        self.port = httpd.server_address[1]
        self.url = "http://%s:%d" % (httpd.server_address[0], self.port)

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    stop = close

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def serve(port=0, addr="127.0.0.1", registry=None):
    """Start a daemon-thread HTTP server exposing ``/metrics``
    (Prometheus text format) and ``/healthz``. ``port=0`` picks a free
    port (read it from the returned handle). Stdlib only — safe to run
    inside an inference deployment next to the Predictor."""
    import http.server

    reg = registry or REGISTRY

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            path, _, query = self.path.partition("?")
            code = 200
            if path == "/metrics":
                body = reg.render_prometheus().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/healthz":
                body = b"ok\n"
                ctype = "text/plain; charset=utf-8"
            elif path == "/traces":
                from . import tracing as _tr
                code, payload = _tr.traces_endpoint(query)
                body = json.dumps(payload).encode() + b"\n"
                ctype = "application/json"
            elif path == "/alerts":
                from . import health as _hl
                code, payload = _hl.alerts_endpoint(query)
                body = json.dumps(payload).encode() + b"\n"
                ctype = "application/json"
            elif path == "/programs":
                from . import forensics as _fx
                code, payload = _fx.programs_endpoint(query)
                body = json.dumps(payload, default=str).encode() + b"\n"
                ctype = "application/json"
            elif path == "/cluster":
                from . import observatory as _ob
                code, payload = _ob.cluster_endpoint(query)
                body = json.dumps(payload, default=str).encode() + b"\n"
                ctype = "application/json"
            else:
                self.send_error(404)
                return
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):   # no stderr chatter per scrape
            pass

    httpd = http.server.ThreadingHTTPServer((addr, port), _Handler)
    httpd.daemon_threads = True
    thread = threading.Thread(target=httpd.serve_forever,
                              name="mxnet-telemetry", daemon=True)
    thread.start()
    set_server_endpoint(addr, httpd.server_address[1])
    return TelemetryServer(httpd, thread)


# ---------------------------------------------------------------------------
# snapshot + diagnostics
# ---------------------------------------------------------------------------

def snapshot():
    """Compact summary for tests, drivers and bug reports: dispatch and
    compile totals plus a live allocator poll (the allocator tracks its
    own peak, so this is meaningful even if no gauge was ever set)."""
    fam = REGISTRY._families.get("op/dispatch_total")
    op_total = sum(c.value for _lv, c in fam.series()) if fam else 0

    def _val(name):
        f = REGISTRY._families.get(name)
        if f is None:
            return 0
        return sum(c.value for _lv, c in f.series())

    out = {"op_dispatch_total": op_total,
           "jit_cache_hits": _val("jit/cache_hits_total"),
           "jit_cache_misses": _val("jit/cache_misses_total"),
           "backend_compile_total": _compile_count,
           "backend_compile_seconds": round(_compile_time, 3),
           # compiled-program registry accounting (programs.py): real
           # backend compiles vs persistent-cache disk loads (their sum
           # is backend_compile_total once the cache is on), registry
           # volume/evictions, and warm-set replay — the cold-start
           # evidence (tests/test_programs.py reads the split)
           "programs_compile_total": _val("programs/compile_total"),
           "programs_disk_hits": _val("programs/disk_hits_total"),
           "programs_registered": _val("programs/registered_total"),
           "programs_registry_hits": _val("programs/registry_hits_total"),
           "programs_evictions": _val("programs/evictions_total"),
           "programs_prewarm_replayed":
               _val("programs/prewarm_replayed_total"),
           "programs_prewarm_skipped":
               _val("programs/prewarm_skipped_total"),
           # fused train-step accounting (executor.train_step): steps
           # run, program builds, and python-cache hit/miss — the
           # O(1)-dispatch-per-step evidence
           "fused_step_total": _val("executor/fused_step_total"),
           "fused_step_compiles": _val("executor/fused_step_compile_total"),
           "fused_step_cache_hits":
               _val("executor/fused_step_cache_hit_total"),
           "fused_step_cache_misses":
               _val("executor/fused_step_cache_miss_total"),
           # serving-path accounting (serve.InferenceEngine): volume,
           # backpressure, and the realized batching efficiency
           # (mean rows a batch, padding waste: below)
           "serve_requests": _val("serving/requests_total"),
           "serve_rejected": _val("serving/rejected_total"),
           "serve_timeouts": _val("serving/timeouts_total"),
           "serve_batches": _val("serving/batches_total"),
           "serve_swaps": _val("serving/swaps_total"),
           # continuous-batching decode accounting (serve.DecodeEngine):
           # token volume, admission refusals, and abnormal slot
           # retirements (preempted, timed out)
           "decode_requests": _val("decode/requests_total"),
           "decode_rejected": _val("decode/rejected_total"),
           "decode_tokens": _val("decode/tokens_total"),
           "decode_preempted": _val("decode/preempted_total"),
           "decode_timeouts": _val("decode/timeouts_total"),
           # fault-tolerance accounting: crash-consistent checkpoint
           # traffic, kvstore transport retries, serve worker crashes,
           # and armed faults fired (test runs) — the robustness
           # evidence (tests/test_fault_tolerance.py reads it)
           "ckpt_saves": _val("checkpoint/saves_total"),
           "ckpt_restores": _val("checkpoint/restores_total"),
           "ckpt_fallbacks": _val("checkpoint/fallbacks_total"),
           "ckpt_corrupt": _val("checkpoint/corrupt_total"),
           "kv_retries": _val("kvstore/retries_total"),
           "kv_giveups": _val("kvstore/giveups_total"),
           # self-healing cluster accounting: server failovers ridden
           # by clients, PS state snapshots (the failover commit
           # record), and ranks re-admitted after being declared dead
           "kv_server_failovers": _val("kvstore/server_failovers_total"),
           "kv_snapshots": _val("kvstore/snapshots_total"),
           "kv_worker_rejoins": _val("kvstore/worker_rejoins_total"),
           "serve_worker_restarts": _val("serving/worker_restarts_total"),
           # quantized-serving accounting: artifacts produced, int8
           # hot-swaps, and the shadow A/B canary volume
           # (the quantize/* counters)
           "quantize_checkpoints": _val("quantize/checkpoints_total"),
           "quantize_swaps": _val("quantize/swaps_total"),
           "quantize_shadow_requests":
               _val("quantize/shadow_requests_total"),
           "quantize_shadow_errors": _val("quantize/shadow_errors_total"),
           "faults_injected": _val("fault/injected_total")}
    # health-layer accounting: firing SLO rules, numerics-sentinel
    # trips, and flight-recorder volume ride every snapshot for
    # free (mx.diagnostics() embeds snapshot())
    try:
        from . import health as _hl
        from . import blackbox as _bb
        out["alerts_firing"] = _hl.alerts_firing()
        out["numerics_trips"] = _hl.numerics_trips()
        out["flight_records"] = _bb.records_written()
    except Exception:
        out["alerts_firing"] = []
        out["numerics_trips"] = 0
        out["flight_records"] = 0
    # compiler-forensics accounting (forensics.py): per-program HLO
    # reports captured vs degraded — whether the run has
    # fusion-level provenance
    out["forensics_captured"] = _val("forensics/captured_total")
    out["forensics_unavailable"] = _val("forensics/unavailable_total")
    # goodput-ledger accounting (goodput.py): what fraction of the
    # run's wall was useful step compute, and where the rest went —
    # present whenever a fit session is live
    try:
        from . import goodput as _gp
        rep = _gp.report()
        if rep.get("active"):
            out["goodput_fraction"] = rep["goodput_fraction"]
            out["badput_fraction"] = rep["badput_fraction"]
            out["goodput_wall_s"] = rep["wall_s"]
            for c, d in rep["categories"].items():
                out["goodput_%s_s" % c] = d["seconds"]
    except Exception:
        pass
    fam = REGISTRY._families.get("serving/batch_rows")
    if fam is not None:
        rows = sum(c.sum for _lv, c in fam.series())
        n = sum(c.count for _lv, c in fam.series())
        if n:
            out["serve_mean_batch_rows"] = round(rows / n, 3)
    fam = REGISTRY._families.get("serving/padding_waste_ratio")
    if fam is not None:
        waste = sum(c.sum for _lv, c in fam.series())
        n = sum(c.count for _lv, c in fam.series())
        if n:
            out["serve_mean_padding_waste"] = round(waste / n, 4)
    try:
        from . import storage
        stats = storage.memory_stats()
        peak = stats.get("peak_bytes_in_use")
        if peak is None:
            f = REGISTRY._families.get("hbm/peak_bytes")
            if f is not None:
                peaks = [c.value for _lv, c in f.series()]
                peak = max(peaks) if peaks else 0
        out["peak_hbm_bytes"] = int(peak or 0)
    except Exception:
        out["peak_hbm_bytes"] = 0
    return out


def diagnostics(as_dict=False):
    """One-shot environment/device/memory/cache report for bug reports —
    the analog of the reference's ``libinfo`` features dump plus the
    storage profiler's summary. Returns a printable string (or the raw
    dict with ``as_dict=True``)."""
    import platform as _plat
    import sys

    from .libinfo import __version__

    info = {"mxnet_tpu": __version__,
            "python": sys.version.split()[0],
            "platform": _plat.platform()}
    try:
        import numpy
        info["numpy"] = numpy.__version__
    except Exception:
        pass
    try:
        import jax
        info["jax"] = jax.__version__
        try:
            info["jax_backend"] = jax.default_backend()
            devs = []
            from . import storage
            for d in jax.devices():
                row = {"id": d.id, "platform": d.platform,
                       "kind": getattr(d, "device_kind", "?")}
                stats = storage.memory_stats(d)
                if stats:
                    row["bytes_in_use"] = stats.get("bytes_in_use")
                    row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
                    row["bytes_limit"] = stats.get("bytes_limit")
                devs.append(row)
            info["devices"] = devs
            info["live_bytes_dev0"] = storage.live_bytes()
        except Exception as e:
            info["jax_backend"] = "unavailable (%s)" % e
    except Exception:
        info["jax"] = "not importable"
    try:
        from .ops import registry as _reg
        ci = _reg._jitted.cache_info()
        info["eager_jit_cache"] = {"entries": ci.currsize, "hits": ci.hits,
                                   "misses": ci.misses}
    except Exception:
        pass
    try:
        # compiled-program registry: how many programs this process
        # holds, what building them cost, and whether a persistent
        # cache dir is wired (the cold-start posture of this replica)
        from . import programs as _pg
        st = _pg.stats()
        if st["entries"] or st["cache_dir"]:
            info["program_registry"] = st
    except Exception:
        pass
    from . import profiler
    info["profiler_running"] = profiler.is_running()
    info["telemetry_enabled"] = _enabled
    info["telemetry"] = snapshot()
    try:
        # support-ticket snapshot: where did recent slow/errored
        # requests or steps spend their time, and is the serving path
        # alive right now
        from . import tracing as _tr
        info["tracing_enabled"] = _tr.enabled()
        info["recent_slow_traces"] = [
            {"trace_id": t["trace_id"], "root": t["root"],
             "duration_ms": t["duration_ms"], "error": t["error"],
             "phases": t["phases"]}
            for t in _tr.slow_traces(limit=5)]
        ex = exemplars()
        if ex:
            info["latency_exemplars"] = ex
    except Exception:
        pass
    try:
        # one-shot health summary: current roofline utilization,
        # whatever SLO rules are firing right now, and the tail of the
        # flight recorder (what the process did last) — the first three
        # things a production incident asks for
        from . import health as _hl
        from . import blackbox as _bb
        hinfo = {"mfu": _hl.mfu_summary(),
                 "alerts_firing": _hl.alerts_firing(),
                 "numerics_mode": _hl.numerics_mode(),
                 "numerics_trips": _hl.numerics_trips()}
        if _bb.enabled():
            hinfo["flight_recorder"] = _bb.path()
            hinfo["flight_tail"] = _bb.tail(20)
        try:
            # compiler forensics: the top-N fusions by bytes moved in
            # the programs farthest from the roofline — which fusion
            # to burn down, straight in the bug report
            from . import forensics as _fx
            wf = _fx.worst_fusions(limit=5)
            if wf:
                hinfo["worst_fusions"] = wf
        except Exception:
            pass
        info["health"] = hinfo
    except Exception:
        pass
    try:
        # goodput ledger: the run's wall-clock cost accounting (every
        # second attributed to step compute / data wait / compile /
        # checkpoint / rescale / restart / straggler wait / idle)
        from . import goodput as _gp
        rep = _gp.report()
        if rep.get("active"):
            info["goodput"] = rep
    except Exception:
        pass
    try:
        # cluster observatory (observatory.py): when one is configured,
        # the bug report carries the one-shot CLUSTER summary — peer
        # count, alerts firing anywhere in the fleet, worst-rank step
        # skew, merged goodput — not just process-local state
        from . import observatory as _ob
        if _ob.configured():
            info["cluster"] = _ob.current().summary()
    except Exception:
        pass
    eng_mod = sys.modules.get("mxnet_tpu.serve.engine")
    if eng_mod is not None:
        try:
            status = eng_mod.engines_status()
            if status:
                info["serve_engines"] = status
        except Exception:
            pass
    try:
        from .config import VARS, get
        # bug reports get pasted into public issues: never include live
        # credential values (e.g. MXNET_TPU_PS_TOKEN)
        info["config"] = {
            k: ("<redacted>" if ("TOKEN" in k or "SECRET" in k
                                 or "PASSWORD" in k) and get(k) else get(k))
            for k in sorted(VARS)}
    except Exception:
        pass
    if as_dict:
        return info
    lines = ["----- mxnet_tpu diagnostics -----"]
    for k, v in info.items():
        if isinstance(v, (dict, list)):
            lines.append("%s:" % k)
            lines.append("  " + json.dumps(v, indent=1, default=str)
                         .replace("\n", "\n  "))
        else:
            lines.append("%s: %s" % (k, v))
    return "\n".join(lines)
