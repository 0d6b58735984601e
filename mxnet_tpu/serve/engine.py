"""Dynamic micro-batching inference engine.

``InferenceEngine`` turns the one-request-at-a-time ``serving.Predictor``
into an online serving path: concurrent requests enter a BOUNDED queue,
worker threads coalesce them into batches padded to a fixed bucket
ladder (serve/batching.py), and one shape-specialized XLA program per
bucket does the compute — so the compile surface is bounded by
``len(buckets)`` regardless of traffic shape, and every chip dispatch
carries as many requests as arrived within the coalescing window.

Production behaviors the bare Predictor lacks, all here:

* **admission control** — a full queue rejects immediately
  (:class:`QueueFullError`, HTTP 503) instead of stretching latency
  unboundedly; queue depth is the knob that trades tail latency for
  acceptance rate.
* **per-request deadlines** — a request that expires while queued is
  failed (:class:`DeadlineExceededError`, HTTP 504) *before* wasting a
  chip dispatch on it.
* **ahead-of-time warmup** — :meth:`warmup` compiles every bucket
  before the server reports healthy, so production traffic never eats
  a compile.
* **graceful drain** — :meth:`close` stops admission, flushes every
  in-flight batch, then joins the workers (what a hot-swap or a
  rolling restart needs).

Telemetry (scraped via serve/http.py or ``telemetry.serve``):
``serving/queue_depth`` gauge, ``serving/batch_rows`` +
``serving/padding_waste_ratio`` histograms, the
``serving/queue_wait_seconds`` vs ``serving/compute_seconds`` latency
split, and ``serving/{rejected,timeouts}_total`` counters.
"""
from __future__ import annotations

import threading
import weakref
from collections import deque

import numpy as _np

from .. import fault as _fault
from ..base import MXNetError
from .. import health as _health
from .. import programs as _pg
from .. import telemetry as _tm
from .. import tracing as _tr
from .batching import parse_buckets, pick_bucket, validate_buckets

__all__ = ["ServeConfig", "InferenceEngine", "QueueFullError",
           "DeadlineExceededError", "EngineClosedError", "engines_status"]

# live engines of this process, for mxnet_tpu.diagnostics(): serve queue
# depth + worker liveness belong in a support-ticket snapshot
_ENGINES = weakref.WeakSet()


def engines_status():
    """One status row per live InferenceEngine (queue depth, worker
    liveness, restart-budget burn) — surfaced by
    ``mxnet_tpu.diagnostics()``."""
    out = []
    for eng in list(_ENGINES):
        if not eng._accepting and not eng._workers:
            # cleanly closed (close()'s own already-closed test), just
            # not GC'd yet — noise in a support snapshot, unlike a
            # draining or dead-crew engine which must stay visible
            continue
        out.append({
            "ready": eng.ready,
            "accepting": eng._accepting,
            "queue_depth": len(eng._queue),
            "workers": len(eng._workers),
            "workers_alive": sum(t.is_alive() for t in eng._workers),
            "restarts_used": eng._restarts_used,
            "buckets": list(eng._cfg.buckets)})
    return out


class QueueFullError(MXNetError):
    """Admission control rejected the request (map to HTTP 503)."""


class DeadlineExceededError(MXNetError):
    """The request's deadline expired before compute (map to HTTP 504)."""


class EngineClosedError(MXNetError):
    """The engine is draining or closed (map to HTTP 503)."""


class ServeConfig(object):
    """Serving knobs. Defaults come from the ``MXNET_SERVE_*`` config
    tier (config.py); constructor arguments override per engine."""

    __slots__ = ("max_batch", "buckets", "queue_depth", "batch_wait",
                 "default_timeout", "workers", "worker_restarts")

    def __init__(self, max_batch=None, buckets=None, queue_depth=None,
                 batch_wait_ms=None, default_timeout_ms=None, workers=None,
                 worker_restarts=None):
        from ..config import get as _cfg

        def pick(val, name):
            return _cfg(name) if val is None else val

        self.max_batch = int(pick(max_batch, "MXNET_SERVE_MAX_BATCH"))
        spec = buckets if buckets is not None \
            else _cfg("MXNET_SERVE_BUCKETS")
        if isinstance(spec, (tuple, list)):
            self.buckets = validate_buckets(spec)
        else:
            self.buckets = parse_buckets(spec, self.max_batch)
        # the ladder caps the admissible request size
        self.max_batch = self.buckets[-1]
        self.queue_depth = int(pick(queue_depth, "MXNET_SERVE_QUEUE_DEPTH"))
        self.batch_wait = float(
            pick(batch_wait_ms, "MXNET_SERVE_BATCH_WAIT_MS")) / 1e3
        self.default_timeout = float(
            pick(default_timeout_ms, "MXNET_SERVE_DEADLINE_MS")) / 1e3
        self.workers = max(1, int(pick(workers, "MXNET_SERVE_WORKERS")))
        self.worker_restarts = max(0, int(pick(
            worker_restarts, "MXNET_SERVE_WORKER_RESTARTS")))
        if self.queue_depth < 1:
            raise MXNetError("queue_depth must be >= 1")


class _Request(object):
    """One submitted inference request; a thread-event future."""

    __slots__ = ("feed", "rows", "deadline", "t_enq", "_event", "outputs",
                 "error", "_tc_lock", "_timeout_counted", "tctx")

    def __init__(self, feed, rows, deadline, tctx=None):
        self.feed = feed
        self.rows = rows
        self.deadline = deadline
        self.t_enq = _tm.monotonic()
        self._event = threading.Event()
        self.outputs = None
        self.error = None
        self._tc_lock = threading.Lock()
        self._timeout_counted = False
        # span context carried across the queue (explicit handoff: the
        # worker thread has no view of the submitter's contextvars)
        self.tctx = tctx

    def _count_timeout(self):
        """Bump serving/timeouts_total ONCE per request, whether the
        expiry is noticed client-side (result() wait), worker-side
        (dequeue past deadline), or both racing."""
        with self._tc_lock:
            if self._timeout_counted:
                return
            self._timeout_counted = True
        _tm.counter("serving/timeouts_total",
                    "Requests failed on deadline expiry").inc()
        # a timed-out trace is always worth keeping as an exemplar
        # (only THIS request's trace: an untraced request must not
        # flag whatever ambient span the waiting thread happens to
        # be under via mark_error's active() fallback)
        if self.tctx is not None:
            _tr.mark_error("deadline exceeded", ctx=self.tctx)

    def set_result(self, outputs):
        self.outputs = outputs
        self._event.set()

    def set_error(self, exc):
        self.error = exc
        self._event.set()

    def wait(self, timeout=None):
        """Block until completion; True when a result/error is set."""
        return self._event.wait(timeout)

    def result(self):
        """Outputs (list of np arrays, one per graph output), waiting at
        most until the request's absolute deadline; raises the request's
        error, or :class:`DeadlineExceededError` at deadline expiry."""
        if self.deadline is None:
            self.wait()
        elif not self.wait(max(0.0, self.deadline - _tm.monotonic())
                           + 0.05):
            self._count_timeout()
            raise DeadlineExceededError(
                "no result within the %.0f ms deadline"
                % ((self.deadline - self.t_enq) * 1e3))
        if self.error is not None:
            raise self.error
        return self.outputs


class InferenceEngine(object):
    """Micro-batching execution engine over one bound model.

    Parameters
    ----------
    predictor : serving.Predictor
        The bound model. Its input shapes define the per-row feature
        shapes (axis 0 is the batch axis on every input); per-bucket
        executors are derived with :meth:`Predictor.reshape`, which
        shares the device-resident parameter buffers — N buckets cost
        one copy of the weights in HBM.
    config : ServeConfig, optional
    """

    def __init__(self, predictor, config=None):
        self._cfg = config or ServeConfig()
        self._base = predictor
        self._input_names = list(predictor._input_names)
        if not self._input_names:
            raise MXNetError("predictor was bound without input_shapes; "
                             "the engine needs named inputs")
        self._feature = {}
        self._dtypes = {}
        for k in self._input_names:
            arr = predictor._exe.arg_dict[k]
            if len(arr.shape) < 1:
                raise MXNetError("input %r is a scalar; the batch axis "
                                 "(axis 0) is required" % k)
            self._feature[k] = tuple(arr.shape[1:])
            self._dtypes[k] = arr.dtype
        self._preds = {}                 # bucket -> Predictor
        self._pred_locks = {}            # bucket -> forward lock
        self._bucket_cost = {}           # bucket -> cost record | None
        self._cost_tag = None            # unique registry tag, lazy
        # graph fingerprint for the compiled-program registry: engines
        # over the same symbol share bucket programs in-process (a
        # hot-swap replacement warms as cache hits) and identify their
        # warm-set manifest entries across processes
        self._graph_hash = _pg.graph_hash(predictor._sym)
        self._warm_report = None
        self._build_lock = threading.Lock()
        self._queue = deque()
        self._cond = threading.Condition()
        self._accepting = True
        self._ready = False
        self._workers = []
        self._restarts_used = 0
        _ENGINES.add(self)

        self._m_requests = _tm.counter(
            "serving/requests_total", "Inference requests accepted")
        self._m_rejected = _tm.counter(
            "serving/rejected_total",
            "Requests rejected by admission control (full queue / closed)")
        self._m_batches = _tm.counter(
            "serving/batches_total", "Coalesced batches executed")
        self._m_depth = _tm.gauge(
            "serving/queue_depth", "Requests waiting in the serve queue")
        self._m_batch_rows = _tm.histogram(
            "serving/batch_rows", "Real rows per executed batch",
            buckets=tuple(float(b) for b in self._cfg.buckets))
        self._m_waste = _tm.histogram(
            "serving/padding_waste_ratio",
            "Padding rows / bucket rows per executed batch",
            buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9))
        self._m_qwait = _tm.histogram(
            "serving/queue_wait_seconds",
            "Time a request waited before its batch launched")
        self._m_compute = _tm.histogram(
            "serving/compute_seconds",
            "Forward wall time per batch (pad + run + fetch)")
        self._m_latency = _tm.histogram(
            "serving/request_seconds",
            "Inference request latency (host-side, submit to result)")

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        """Spawn the worker thread(s). Idempotent."""
        with self._cond:
            if self._workers:
                return self
            self._accepting = True
            self._restarts_used = 0
            for i in range(self._cfg.workers):
                t = threading.Thread(target=self._worker_main,
                                     name="mxnet-serve-worker-%d" % i,
                                     daemon=True)
                t.start()
                self._workers.append(t)
        return self

    def warmup(self, use_manifest=True):
        """Ahead-of-time compile every bucket's forward program (zeros
        feed, fetched to host so compile + first execute both finish).
        The server must not report healthy before this returns: after
        it, steady-state traffic never triggers an XLA compile.

        Routes through :func:`programs.prewarm`: the configured ladder
        plus any warm-set manifest entries for this graph replay here —
        a fresh replica loads every program from the persistent cache
        on disk instead of running XLA (``programs/disk_hits_total`` vs
        ``programs/compile_total`` tells them apart; the report lands
        in :attr:`warm_report`)."""
        include = [("serve_bucket", self._bucket_spec(b))
                   for b in self._cfg.buckets]
        self._warm_report = _pg.prewarm(
            sites={"serve_bucket": self._warm_bucket_spec},
            include=include, graph=self._graph_hash,
            use_manifest=use_manifest)
        self._ready = True
        return self

    @property
    def warm_report(self):
        """The last :meth:`warmup`'s prewarm report (replayed/compile/
        disk-hit counts and wall), or None before the first warmup."""
        return self._warm_report

    def _bucket_spec(self, bucket):
        """Abstract input spec of one bucket program — what the
        warm-set manifest stores so a future replica can replay the
        trace without a request's worth of knowledge."""
        return {"bucket": int(bucket),
                "inputs": {k: [[int(bucket)] + list(self._feature[k]),
                               str(_np.dtype(self._dtypes[k]))]
                           for k in self._input_names}}

    def _warm_bucket_spec(self, spec):
        """Prewarm replay callable: compile + execute one bucket from
        its abstract spec. Manifest entries that don't fit THIS engine
        (a bucket outside the configured ladder, or a same-symbol model
        bound at other feature shapes) are ignored — pick_bucket would
        never route traffic to them."""
        b = int(spec.get("bucket", 0))
        if b not in self._cfg.buckets:
            return False
        for k, ent in (spec.get("inputs") or {}).items():
            if k not in self._feature:
                return False
            if tuple(ent[0][1:]) != self._feature[k]:
                return False
        feed = {k: _np.zeros((b,) + self._feature[k],
                             dtype=self._dtypes[k])
                for k in self._input_names}
        pred = self._bucket_pred(b)
        with self._pred_locks[b]:
            outs = pred._exe.forward(is_train=False, **feed)
            for o in outs:
                o.asnumpy()
        self._note_bucket_cost(b, pred)
        _pg.note_warm("serve_bucket", self._graph_hash,
                      self._bucket_spec(b))

    def _note_bucket_cost(self, bucket, pred):
        """Alias the bucket forward's cost-analysis capture (taken by
        the executor on its first forward) under this ENGINE's bucket
        so measured compute walls turn into per-bucket serving/mfu.
        The registry key carries a process-unique engine tag: two live
        engines (shadow A/B, swap drain) must never share a record."""
        if bucket not in self._bucket_cost:
            if self._cost_tag is None:
                self._cost_tag = _health.next_cost_key("eng")
            self._bucket_cost[bucket] = _health.register_cost(
                "serve_bucket", "%s/%s" % (self._cost_tag, bucket),
                pred._exe.forward_cost(False))
        return self._bucket_cost[bucket]

    @property
    def ready(self):
        """Health-check gate: every bucket compiled AND at least one
        worker actually alive — a warmed engine whose crew all crashed
        past the restart budget (or that has no one to pop the queue)
        must not attract load-balancer traffic; /healthz degrades to
        not-ready and the balancer routes elsewhere."""
        return self._ready and any(t.is_alive() for t in self._workers)

    @property
    def config(self):
        return self._cfg

    def engine(self):
        """Uniform access for the HTTP frontend (ModelRegistry has the
        same method returning its *current* engine)."""
        return self

    def close(self, drain=True, timeout=30.0):
        """Stop admission; with ``drain`` flush every queued request
        through the model, else fail them with EngineClosedError. Then
        join the workers."""
        with self._cond:
            if not self._accepting and not self._workers:
                return
            self._accepting = False
            if not drain or not self._workers:
                # no worker will ever pop these: failing them beats a
                # future that never resolves (drain needs live workers)
                while self._queue:
                    req = self._queue.popleft()
                    req.set_error(EngineClosedError("engine closed"))
                self._m_depth.set(0)
            self._cond.notify_all()
        for t in self._workers:
            t.join(timeout=timeout)
        # a worker that outlived the join timeout (forward hung on the
        # device) stays tracked: start() must not spawn a second crew
        # over the same queue, and callers can see the drain was partial
        self._workers = [t for t in self._workers if t.is_alive()]
        self._ready = False

    # -- request path ------------------------------------------------------
    def submit(self, feed, timeout_ms=None, ctx=None):
        """Enqueue one request; returns its future (:class:`_Request`).

        ``feed``: ``{input_name: array-like}`` with every input carrying
        the same axis-0 row count ``1 <= rows <= max_batch``. Raises
        :class:`QueueFullError` immediately when the queue is at depth
        (admission control — never unbounded latency) and
        :class:`EngineClosedError` when draining/closed.

        ``ctx``: optional :class:`tracing.SpanContext` the batch worker
        parents its spans under (the HTTP frontend passes its request
        root); defaults to the caller's active context.

        Requests submitted before :meth:`start` queue up and are served
        once the workers spawn (deliberate: fill-then-start); on an
        engine that is never started they can only expire against their
        deadline, or fail at :meth:`close`.
        """
        feed, rows = self._check_feed(feed)
        timeout = (self._cfg.default_timeout if timeout_ms is None
                   else float(timeout_ms) / 1e3)
        deadline = (_tm.monotonic() + timeout) if timeout > 0 else None
        req = _Request(feed, rows, deadline,
                       tctx=ctx if ctx is not None else _tr.active())
        with self._cond:
            if not self._accepting:
                self._m_rejected.inc()
                raise EngineClosedError("engine is draining/closed")
            if len(self._queue) >= self._cfg.queue_depth:
                self._m_rejected.inc()
                raise QueueFullError(
                    "serve queue full (%d requests); retry later"
                    % self._cfg.queue_depth)
            self._queue.append(req)
            self._m_requests.inc()
            self._m_depth.set(len(self._queue))
            self._cond.notify()
        return req

    def predict(self, feed, timeout_ms=None):
        """Synchronous convenience: submit + wait + unpack."""
        return self.submit(feed, timeout_ms).result()

    def _check_feed(self, feed):
        if not isinstance(feed, dict):
            if len(self._input_names) != 1:
                raise MXNetError(
                    "model has inputs %s; pass a feed dict"
                    % self._input_names)
            feed = {self._input_names[0]: feed}
        missing = [k for k in self._input_names if k not in feed]
        if missing:
            raise MXNetError("feed missing inputs %s" % missing)
        out, rows = {}, None
        for k in self._input_names:
            arr = _np.asarray(feed[k], dtype=self._dtypes[k])
            if arr.ndim == len(self._feature[k]):
                arr = arr[None]          # single row without batch axis
            if tuple(arr.shape[1:]) != self._feature[k]:
                raise MXNetError(
                    "input %r has feature shape %s, model expects %s"
                    % (k, tuple(arr.shape[1:]), self._feature[k]))
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                raise MXNetError("inputs disagree on the batch axis")
            out[k] = arr
        if rows < 1:
            raise MXNetError("empty request (0 rows)")
        if rows > self._cfg.max_batch:
            raise MXNetError(
                "request of %d rows exceeds max_batch=%d; split it "
                "client-side" % (rows, self._cfg.max_batch))
        return out, rows

    # -- batching worker ---------------------------------------------------
    def _take_batch(self):
        """Pop a coalesced FIFO run of requests totalling at most
        ``max_batch`` rows, waiting up to ``batch_wait`` after the first
        arrival for more to coalesce. None = engine closed and empty;
        otherwise ``(batch, t_coalesce0, t_coalesce1)`` — the window
        bounds feed the ``serve.coalesce`` trace span."""
        with self._cond:
            while not self._queue:
                if not self._accepting:
                    return None
                self._cond.wait(0.1)
            t_co0 = _tm.monotonic()
            batch = [self._queue.popleft()]
            rows = batch[0].rows

            def grab():
                r = rows
                while (self._queue
                       and r + self._queue[0].rows <= self._cfg.max_batch):
                    req = self._queue.popleft()
                    batch.append(req)
                    r += req.rows
                return r

            rows = grab()
            if self._cfg.batch_wait > 0:
                t_end = _tm.monotonic() + self._cfg.batch_wait
                while rows < self._cfg.max_batch and self._accepting:
                    if self._queue:      # strict FIFO: a head that no
                        break            # longer fits ends the window
                    remaining = t_end - _tm.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                    rows = grab()
            self._m_depth.set(len(self._queue))
            if self._queue:
                self._cond.notify()      # more work for another worker
        return batch, t_co0, _tm.monotonic()

    def _worker_main(self):
        """Worker thread entry: run the loop, and when it CRASHES (an
        exception escaping the per-batch containment — a bug, an
        injected ``serve.worker`` fault, a device wedge) restart it in
        place, up to ``MXNET_SERVE_WORKER_RESTARTS`` restarts shared
        across the crew. Each restart is counted in
        ``serving/worker_restarts_total``; past the budget the worker
        stays down and ``ready`` (hence /healthz) degrades once no
        worker is left alive."""
        while True:
            try:
                self._worker_loop()
                return                   # clean exit: engine closed
            except BaseException as exc:
                with self._cond:
                    if not self._accepting:
                        return           # crash during drain: no restart
                    if self._restarts_used >= self._cfg.worker_restarts:
                        import logging
                        logging.error(
                            "serve worker crashed (%s) with the restart "
                            "budget (%d) exhausted; worker stays down",
                            exc, self._cfg.worker_restarts)
                        return
                    self._restarts_used += 1
                # counted only when a restart actually happens — the
                # metric is the alerting signal for budget burn-down
                _tm.counter("serving/worker_restarts_total",
                            "Serve worker threads restarted after a "
                            "crash").inc()

    def _worker_loop(self):
        while True:
            _fault.inject("serve.worker")
            taken = self._take_batch()
            if taken is None:
                return
            batch, t_co0, t_co1 = taken
            try:
                self._run_batch(batch, t_co0, t_co1)
            except Exception as exc:     # never let the worker die: fail
                err = MXNetError(        # the batch, keep serving
                    "batch processing failed: %s" % exc)
                for req in batch:
                    if not req._event.is_set():
                        req.set_error(err)

    def _run_batch(self, batch, t_co0=None, t_co1=None):
        now = _tm.monotonic()
        live = []
        for req in batch:
            if req.deadline is not None and now > req.deadline:
                req._count_timeout()
                if req.tctx is not None and req.tctx.sampled:
                    # the retained 504 exemplar is exactly the trace
                    # that needs its breakdown: all its time was queue
                    _tr.record_span("serve.queue_wait", req.tctx,
                                    req.t_enq, now)
                req.set_error(DeadlineExceededError(
                    "deadline expired after %.0f ms in queue"
                    % ((now - req.t_enq) * 1e3)))
            else:
                live.append(req)
        if not live:
            return
        rows = sum(r.rows for r in live)
        bucket = pick_bucket(rows, self._cfg.buckets)
        traced = [r for r in live if r.tctx is not None and r.tctx.sampled]
        t_pad0 = _tm.monotonic()
        if len(live) == 1 and live[0].rows == bucket:
            feed = live[0].feed          # exact fit: zero host copies
        else:
            # one zeroed bucket buffer per input, each request's rows
            # copied in once (padding comes free)
            feed = {}
            for k in self._input_names:
                buf = _np.zeros((bucket,) + self._feature[k],
                                dtype=self._dtypes[k])
                offset = 0
                for r in live:
                    buf[offset:offset + r.rows] = r.feed[k]
                    offset += r.rows
                feed[k] = buf
        t_pad1 = _tm.monotonic()

        # the batch is ONE unit of work fanning in N request parents:
        # its spans get one shared id each, recorded into every
        # participating trace. Nested executor spans adopt the batch
        # leader's context (first traced request).
        batch_sid = _tr.new_span_id() if traced else None
        comp_sid = _tr.new_span_id() if traced else None
        leader = traced[0].tctx if traced else None
        # nested executor spans adopt the leader's trace, parented under
        # the (to-be-recorded) serve.compute span
        compute_ctx = (leader.child_of(comp_sid)
                       if leader is not None else None)
        t0 = _tm.monotonic()
        try:
            pred = self._bucket_pred(bucket)
            with self._pred_locks[bucket]:
                with _tr.use_context(compute_ctx):
                    outs = pred._exe.forward(is_train=False, **feed)
                    outs_np = [o.asnumpy() for o in outs]
        except Exception as exc:          # surface, don't kill the worker
            err = MXNetError("batch execution failed: %s" % exc)
            for req in live:
                _tr.mark_error(err, ctx=req.tctx)
                req.set_error(err)
            return
        t1 = _tm.monotonic()

        self._m_batches.inc()
        self._m_batch_rows.observe(rows)
        self._m_waste.observe((bucket - rows) / float(bucket))
        self._m_compute.observe(
            t1 - t0, trace_id=leader.trace_id if leader else None)
        _health.note_serve_batch(bucket, t1 - t0,
                                 self._note_bucket_cost(bucket, pred))
        exact_fit = len(live) == 1 and live[0].rows == outs_np[0].shape[0]
        offset = 0
        results = []
        t_slice0 = _tm.monotonic()
        for req in live:
            if exact_fit:
                results.append(outs_np)
            else:
                # copy the rows out: a view would pin the whole padded
                # bucket output for the lifetime of each request future
                results.append([o[offset:offset + req.rows].copy()
                                for o in outs_np])
            offset += req.rows
        t_slice1 = _tm.monotonic()

        if traced:
            # record spans BEFORE delivering results: the submitter's
            # root span may close the trace the instant result() returns
            pad_sid = _tr.new_span_id()
            slice_sid = _tr.new_span_id()
            co_sid = _tr.new_span_id() if t_co0 is not None else None
            battrs = {"rows": rows, "bucket": bucket, "fanin": len(live)}
            for req in traced:
                ctx = req.tctx
                _tr.record_span("serve.queue_wait", ctx, req.t_enq, now)
                _tr.record_span("serve.batch", ctx, t_co0 or t_pad0,
                                t_slice1, span_id=batch_sid,
                                parent_id=ctx.span_id, attrs=battrs)
                if co_sid is not None:
                    _tr.record_span("serve.coalesce", ctx, t_co0, t_co1,
                                    span_id=co_sid, parent_id=batch_sid)
                _tr.record_span("serve.pad", ctx, t_pad0, t_pad1,
                                span_id=pad_sid, parent_id=batch_sid)
                _tr.record_span("serve.compute", ctx, t0, t1,
                                span_id=comp_sid, parent_id=batch_sid,
                                attrs={"bucket": bucket})
                _tr.record_span("serve.slice", ctx, t_slice0, t_slice1,
                                span_id=slice_sid, parent_id=batch_sid)

        for req, res in zip(live, results):
            req.set_result(res)
            self._m_qwait.observe(t0 - req.t_enq)
            self._m_latency.observe(
                t1 - req.t_enq,
                trace_id=req.tctx.trace_id if req.tctx else None)

    # -- bucket executors --------------------------------------------------
    def _bucket_pred(self, bucket):
        """Predictor bound at ``bucket`` rows. Built once per bucket;
        parameters are shared device buffers (Predictor.reshape), so the
        ladder costs one weight copy in HBM plus len(buckets) compiled
        programs."""
        pred = self._preds.get(bucket)
        if pred is not None:
            return pred
        with self._build_lock:
            pred = self._preds.get(bucket)
            if pred is None:
                base_rows = self._base._exe.arg_dict[
                    self._input_names[0]].shape[0]
                if base_rows == bucket:
                    pred = self._base
                else:
                    shapes = {k: (bucket,) + self._feature[k]
                              for k in self._input_names}
                    pred = self._base.reshape(shapes)
                self._pred_locks[bucket] = threading.Lock()
                self._preds[bucket] = pred
        return pred
