"""Continuous-batching autoregressive decode serving.

The micro-batching :class:`~mxnet_tpu.serve.engine.InferenceEngine`
(PR 3) is batch-at-admission: every request in a batch enters and
leaves together, which is the right shape for stateless scoring and the
wrong shape for autoregressive decode — one long generation holds the
whole batch hostage and a short request pays worst-case latency.
:class:`DecodeEngine` schedules at ITERATION granularity instead:

* the scheduler loop admits and retires requests **every decode
  step** — a finishing sequence's slot is reassigned on the next
  iteration, not at end-of-batch;
* **prefill** and **decode** are separate bucketed phases: prompts
  prefill through a power-of-two ladder on prompt length (one batched
  causal forward per admission — MXU-width matmuls), decode runs at
  fixed slot-count buckets with every live sequence at its own depth;
* the KV cache lives in a preallocated HBM **page pool** with
  per-request block tables (serve/kv_pages.py +
  ``parallel.transformer.PagedKVCache``), so the decode step is ONE
  donated jitted program per slot bucket — traffic of arbitrary mixed
  prompt/output lengths compiles ``len(prefill_buckets) +
  len(slot_buckets)`` XLA programs, ever (the serve bucket ladder's
  compile-cache discipline, extended to stateful decode);
* **admission control** refuses work the page pool cannot cover for
  the request's whole lifetime (prompt + max_new_tokens) — a 503
  through the existing :class:`QueueFullError` path, with page
  exhaustion distinct from queue depth in the error detail — so a
  running sequence is never evicted for memory;
* tokens **stream** as they are produced (:meth:`DecodeSession.tokens`
  / ``POST /generate`` chunked responses in serve/http.py), under the
  standard deadline/tracing machinery: per-step ``decode.step`` /
  ``decode.prefill`` / ``decode.schedule`` spans fan into every
  participating request trace exactly like ``serve.batch`` does, and
  the scheduler's own loop logs one ``decode.iteration`` per pass with
  its prefills and its step under it, context or none.

Decoding is greedy (argmax) — deliberately: the acceptance contract is
that batched continuous decode is BITWISE-identical to per-request
unbatched :func:`~mxnet_tpu.parallel.transformer.transformer_decode_step`
decode, and tests/test_decode_serve.py asserts it token-for-token.

Telemetry: ``decode/tokens_total``, ``decode/slot_occupancy``,
``decode/page_pool_free``, ``decode/prefill_seconds`` /
``decode/step_seconds``, ``decode/preempted_total``,
``decode/timeouts_total``, ``decode/worker_restarts_total``.
Knobs: ``MXNET_DECODE_*`` (config.py). Docs: docs/decode_serving.md.
"""
from __future__ import annotations

import concurrent.futures as _futures
import functools
import queue as _queue
import threading
from collections import deque

import numpy as _np

from .. import fault as _fault
from .. import health as _health
from .. import programs as _pg
from .. import telemetry as _tm
from .. import tracing as _tr
from ..base import MXNetError
from .batching import pick_bucket, power_of_two_buckets
from .engine import (DeadlineExceededError, EngineClosedError,
                     QueueFullError)
from .kv_pages import PagePool, PagePoolExhausted, pages_needed

__all__ = ["DecodeConfig", "DecodeEngine", "DecodeSession"]

_SENTINEL = object()


def _prefill_variant():
    """Kernel-variant tag carried in every ``decode_prefill`` registry /
    forensics key: prefill attention rides the Pallas flash kernel on
    TPU and plain XLA elsewhere, so ``forensics --diff`` across this
    boundary compares like with like instead of silently overwriting
    the xla-prefill baseline record with the pallas one (stale manifest
    entries under the old key are skipped by prewarm, not replayed)."""
    from ..ops.pallas.flash_attention import on_tpu
    return "pallas-prefill" if on_tpu() else "xla-prefill"


class DecodeConfig(object):
    """Decode-serving knobs. Defaults come from the ``MXNET_DECODE_*``
    config tier; constructor arguments override per engine."""

    __slots__ = ("slots", "page_size", "num_pages", "window_pages",
                 "max_context", "queue_depth", "max_new_tokens",
                 "default_timeout", "worker_restarts", "prefill_buckets",
                 "slot_buckets")

    def __init__(self, slots=None, page_size=None, num_pages=None,
                 max_context=None, queue_depth=None, max_new_tokens=None,
                 default_timeout_ms=None, worker_restarts=None,
                 window_pages=None):
        from ..config import get as _cfg

        def pick(val, name):
            return _cfg(name) if val is None else val

        self.slots = int(pick(slots, "MXNET_DECODE_SLOTS"))
        self.page_size = int(pick(page_size, "MXNET_DECODE_PAGE_SIZE"))
        self.num_pages = int(pick(num_pages, "MXNET_DECODE_NUM_PAGES"))
        # pages of the window layers' pool, for a model that has such
        # layers (then ``num_pages`` is the global layers'); None =
        # a whole ring for every slot (queued requests hold theirs too)
        self.window_pages = (None if window_pages is None
                             else int(window_pages))
        self.max_context = int(pick(max_context,
                                    "MXNET_DECODE_MAX_CONTEXT"))
        self.queue_depth = int(pick(queue_depth,
                                    "MXNET_DECODE_QUEUE_DEPTH"))
        self.max_new_tokens = int(pick(max_new_tokens,
                                       "MXNET_DECODE_MAX_NEW_TOKENS"))
        self.default_timeout = float(pick(
            default_timeout_ms, "MXNET_DECODE_DEADLINE_MS")) / 1e3
        self.worker_restarts = max(0, int(pick(
            worker_restarts, "MXNET_SERVE_WORKER_RESTARTS")))
        if self.slots < 1:
            raise MXNetError("slots must be >= 1")
        if self.queue_depth < 1:
            raise MXNetError("queue_depth must be >= 1")
        if self.page_size < 1:
            raise MXNetError("page_size must be >= 1")
        if self.max_context % self.page_size:
            raise MXNetError(
                "max_context=%d must be a multiple of page_size=%d "
                "(positions map to whole pages)"
                % (self.max_context, self.page_size))
        # prefill ladder: page_size, 2*ps, 4*ps, ... capped at
        # max_context (appended as the final bucket when not already a
        # rung) — every bucket a page multiple, so the prefill page
        # write is a pure reshape-scatter
        buckets, b = [], self.page_size
        while b < self.max_context:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_context)
        self.prefill_buckets = tuple(buckets)
        self.slot_buckets = power_of_two_buckets(self.slots)

    @property
    def pages_per_seq(self):
        return self.max_context // self.page_size


class DecodeSession(object):
    """One admitted generation request: a token STREAM plus its page
    reservation and decode cursor. Produced tokens arrive on a
    thread-safe queue as the scheduler emits them; consume with
    :meth:`tokens` / :meth:`next_token` (streaming) or :meth:`result`
    (wait for the full generation)."""

    __slots__ = ("prompt", "prompt_len", "max_new_tokens", "stop_token",
                 "deadline", "t_enq", "t_admit", "t_first", "t_done",
                 "tctx", "page_ids", "block_table", "window_page_ids",
                 "window_block_table", "state_row", "pos", "last_token",
                 "generated", "out_tokens", "expert_choices", "error",
                 "_q", "_finished")

    def __init__(self, prompt, max_new_tokens, stop_token, deadline,
                 tctx):
        self.prompt = prompt
        self.prompt_len = len(prompt)
        self.max_new_tokens = max_new_tokens
        self.stop_token = stop_token
        self.deadline = deadline
        self.t_enq = _tm.monotonic()
        self.t_admit = None
        self.t_first = None
        self.t_done = None
        self.tctx = tctx
        self.page_ids = None
        self.block_table = None
        self.window_page_ids = None      # a model with window layers:
        self.window_block_table = None   # its ring in their pool
        self.state_row = None            # a model with linear layers: its
                                         # row of their state pools
        self.pos = 0                     # next position to WRITE
        self.last_token = None           # feeds the next decode step
        self.generated = 0
        self.out_tokens = []
        # a model with a drop-free router: the experts the programs that
        # served this request chose, (layers, positions, top_k) arrays in
        # order — the prompt's, then one position a decode step (the
        # last token emitted is never fed, so never routed)
        self.expert_choices = []
        self.error = None
        self._q = _queue.Queue()
        self._finished = False

    # -- producer side (scheduler thread) ---------------------------------
    def _emit(self, tok):
        if self.t_first is None:
            self.t_first = _tm.monotonic()
        self.out_tokens.append(tok)
        self.generated += 1
        self.last_token = tok
        self._q.put(tok)

    def _finish(self, error=None):
        if self._finished:
            return
        self._finished = True
        self.error = error
        self.t_done = _tm.monotonic()
        if error is not None and self.tctx is not None:
            _tr.mark_error(error, ctx=self.tctx)
        self._q.put(_SENTINEL)

    @property
    def done(self):
        return self._finished

    # -- consumer side ----------------------------------------------------
    def next_token(self, timeout=None):
        """Next generated token id; None when the stream has ended.
        Waits up to ``timeout`` (default: the session deadline); raises
        the session's error — :class:`DeadlineExceededError` when the
        server retired it, or locally when no token arrives in time."""
        if timeout is None and self.deadline is not None:
            timeout = max(0.0, self.deadline - _tm.monotonic()) + 0.25
        try:
            tok = self._q.get(timeout=timeout)
        except _queue.Empty:
            raise DeadlineExceededError(
                "no token within the per-token deadline")
        if tok is _SENTINEL:
            self._q.put(_SENTINEL)       # keep the stream terminal
            if self.error is not None:
                raise self.error
            return None
        return tok

    def tokens(self):
        """Generator over the token stream (blocks between tokens)."""
        while True:
            tok = self.next_token()
            if tok is None:
                return
            yield tok

    def result(self):
        """Every generated token (blocks until the stream ends)."""
        for _ in self.tokens():
            pass
        return list(self.out_tokens)


class DecodeEngine(object):
    """Iteration-level scheduling decode engine over one transformer.

    Parameters
    ----------
    params : pytree
        Transformer parameters (``init_transformer_params`` layout).
    model_cfg : parallel.transformer.TransformerConfig
    config : DecodeConfig, optional

    Weights are traced ARGUMENTS of the compiled programs, so
    :meth:`swap_params` rotates them with zero recompiles; the page
    pool is donated through every prefill/step call (true in-place HBM
    update, no double buffering).
    """

    def __init__(self, params, model_cfg, config=None):
        self._cfg = config or DecodeConfig()
        self._model_cfg = model_cfg
        self._params = params
        self._vocab = int(model_cfg.vocab_size)
        from ..parallel.transformer import kv_layer_kinds
        # a model with sliding-window layers keeps two kinds of cache:
        # its global layers' pages as ever, its window layers' in a
        # pool of their own where a sequence holds a RING of ring_pages
        # entries (serve/kv_pages.py, transformer.HybridKVCache)
        kinds = kv_layer_kinds(model_cfg)
        self._window = ("window" in kinds
                        and int(model_cfg.sliding_window))
        self._ring_pages = self._wpool = None
        # a model with linear-attention layers keeps a second kind of
        # state beside its full layers' pages: a fixed-size STATE ROW a
        # live session, in pools of their own (transformer.
        # LinearStateCache). A session in a slot holds one and a queued
        # one none, so there is a row a slot and row 0, the null row of a
        # step bucket's dummy slots: nothing to configure
        self._linear_layers = kinds.count("linear")
        self._state_rows = (PagePool(self._cfg.slots + 1, kind="state")
                            if self._linear_layers else None)
        if self._window:
            self._ring_pages = min(
                pages_needed(self._window, self._cfg.page_size) + 1,
                self._cfg.pages_per_seq)
            if self._cfg.window_pages is None:
                self._cfg.window_pages = (self._cfg.slots
                                          * self._ring_pages + 1)
            self._wpool = PagePool(self._cfg.window_pages, kind="window")
        # a latent-attention model keeps ONE pool of compressed vectors
        # and no V pool (transformer.LatentKVCache): the programs' v_pages
        # argument is None, and a request holds pages for its positions,
        # not for its prefill bucket
        self._latent = bool(model_cfg.kv_lora_rank)
        self._pool = PagePool(
            self._cfg.num_pages,
            kind="latent" if self._latent
            else "global" if self._window else None)
        # the drop-free expert routers report, in the tokens' own fetch,
        # how many experts each layer's call touched and which ones each
        # row chose
        self._moe = (bool(model_cfg.num_experts)
                     and model_cfg.moe_router != "capacity")
        self._moe_layers = model_cfg.n_layers - model_cfg.dense_layers
        # the experts this engine's device holds, of the router's
        local = model_cfg.moe_local_experts or (0, model_cfg.num_experts)
        self._moe_first, self._moe_end = local[0], local[0] + local[1]
        self._k_pages, self._v_pages = self._fresh_pools()
        self._prefill_progs = {}
        self._step_progs = {}
        self._prog_costs = {}            # (phase, bucket) -> rec | None
        # graph fingerprint for the compiled-program registry: the
        # model architecture + parameter layout + page size determine
        # the prefill/step programs (weights are traced arguments)
        import jax as _jax
        psig = [[list(l.shape), str(l.dtype)]
                for l in _jax.tree_util.tree_leaves(params)]
        self._graph_hash = _pg.graph_hash(
            {"model": repr(model_cfg), "params": psig,
             "page_size": int(self._cfg.page_size)})
        self._warm_report = None
        self._cond = threading.Condition()
        self._waiting = deque()
        self._live = []
        self._accepting = True
        self._closing = False
        self._ready = False
        self._worker = None
        self._warmup_req = None
        self._restarts_used = 0
        self._iter_hook = None

        self._m_requests = _tm.counter(
            "decode/requests_total", "Decode requests admitted")
        self._m_rejected = _tm.counter(
            "decode/rejected_total",
            "Decode requests refused at admission (queue depth or page "
            "pool)", ("reason",))
        self._m_tokens = _tm.counter(
            "decode/tokens_total", "Tokens generated (all sessions)")
        self._m_occupancy = _tm.gauge(
            "decode/slot_occupancy",
            "Live decode slots (out of MXNET_DECODE_SLOTS)")
        self._m_free = _tm.gauge(
            "decode/page_pool_free", "Free KV-cache pages in the pool "
            "(the global layers' pool of a model with window layers)")
        self._m_pages_free = _tm.gauge(
            "decode/pages_free", "Free KV-cache pages by the kind of "
            "layer whose pool they are in (global | window | latent: a "
            "latent-attention model's one pool, which keeps every "
            "position and so reads as global too)", ("kind",))
        self._m_state_free = _tm.gauge(
            "decode/state_rows_free", "Free state rows of a model with "
            "linear-attention layers (a live session holds one for its "
            "life; the null row is not counted)")
        self._m_moe_rows = _tm.counter(
            "decode/moe_assignments_total",
            "Token-to-expert assignments of the prompts prefilled and "
            "the tokens decoded (top-k a token a layer; the padding of "
            "a bucket and dummy slots are not counted)")
        self._m_moe_absent = _tm.counter(
            "decode/moe_absent_assignments_total",
            "Of decode/moe_assignments_total, those whose expert this "
            "engine's device does not hold (moe_local_experts): they took "
            "no row here")
        self._m_moe_active = _tm.counter(
            "decode/moe_expert_activations_total",
            "Experts that received at least one row (padding included), "
            "summed over layers and calls: what the grouped expert "
            "product had to read")
        self._m_prefill = _tm.histogram(
            "decode/prefill_seconds",
            "Prefill wall time per admission (bucketed prompt forward)")
        self._m_step = _tm.histogram(
            "decode/step_seconds",
            "Decode step wall time (one token for every live slot)")
        self._m_preempted = _tm.counter(
            "decode/preempted_total",
            "Sessions retired abnormally mid-decode (crash containment "
            "or deadline expiry in a slot)")
        self._m_timeouts = _tm.counter(
            "decode/timeouts_total",
            "Sessions failed on deadline expiry (queued or decoding)")
        self._note_free()

    def _fresh_pools(self):
        from ..parallel.transformer import init_kv_pages
        pages = self._cfg.num_pages
        if self._window:
            pages = (pages, self._cfg.window_pages)
        elif self._linear_layers:
            pages = (pages, self._state_rows.num_pages)
        return init_kv_pages(self._model_cfg, pages, self._cfg.page_size)

    def _note_free(self):
        self._m_free.set(self._pool.free_pages)
        self._m_pages_free.labels("global").set(self._pool.free_pages)
        if self._latent:
            self._m_pages_free.labels("latent").set(self._pool.free_pages)
        if self._wpool is not None:
            self._m_pages_free.labels("window").set(
                self._wpool.free_pages)
        if self._linear_layers:
            self._m_state_free.set(self._state_rows.free_pages)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        """Spawn the scheduler thread. Idempotent."""
        with self._cond:
            if self._worker is not None and self._worker.is_alive():
                return self
            self._closing = False
            self._accepting = True
            self._worker = threading.Thread(
                target=self._worker_main, name="mxnet-decode-scheduler",
                daemon=True)
            self._worker.start()
        return self

    def warmup(self, timeout=600.0):
        """Ahead-of-time compile every prefill bucket and every decode
        slot bucket (writes go to the reserved null page). After this,
        steady-state traffic of ANY prompt/output mix never triggers an
        XLA compile — the jit cache is exactly ``len(prefill_buckets)
        + len(slot_buckets)`` programs.

        The programs are traced and lowered ON the scheduler thread
        (warmup posts a request to the loop and waits): jax's jit cache
        is keyed per thread-local context, so a program lowered on the
        caller's thread can MISS when the scheduler later runs it — a
        stray recompile per bucket on first traffic. Lower where you
        execute; the backend compile of a lowering (or its load from
        the persistent cache) may run anywhere (:meth:`_precompile`)."""
        self.start()
        req = {"event": threading.Event(), "error": None}
        with self._cond:
            self._warmup_req = req
            self._cond.notify_all()
        if not req["event"].wait(timeout):
            raise MXNetError("decode warmup did not finish in %.0fs"
                             % timeout)
        if req["error"] is not None:
            raise req["error"]
        self._ready = True
        return self

    def _do_warmup(self):
        """Compile + execute every bucket program (scheduler thread),
        routed through :func:`programs.prewarm` — the configured
        buckets plus any warm-set manifest entries for this model
        replay here, loading from the persistent compile cache.

        Each program is warmed with :func:`programs.warm_twice`: these
        are DONATED loops (every call donates and returns the page
        pools), so pjit keeps one executable per input-sharding
        provenance and each program must also run against
        pjit-provenance pools — the only provenance steady-state
        traffic ever presents — so any re-specialization compiles
        here, not on the first request."""
        include = ([("decode_prefill", {"bucket": int(b),
                                        "kernel": _prefill_variant()})
                    for b in self._cfg.prefill_buckets]
                   + [("decode_step", {"slots": int(n)})
                      for n in self._cfg.slot_buckets])
        self._precompile()
        self._warm_report = _pg.prewarm(
            sites={"decode_prefill": self._warm_prefill_spec,
                   "decode_step": self._warm_step_spec},
            include=include, graph=self._graph_hash)

    def _prefill_args(self, bucket):
        n_pb = bucket // self._cfg.page_size
        return (self._params, self._k_pages, self._v_pages,
                self._tables(_np.zeros(n_pb, _np.int32),
                             self._second_table()),
                _np.zeros((1, bucket), _np.int32),
                _np.array([bucket], _np.int32))

    def _step_args(self, nslots):
        return (self._params, self._k_pages, self._v_pages,
                self._tables(
                    _np.zeros((nslots, self._cfg.pages_per_seq), _np.int32),
                    self._second_table(nslots)),
                _np.zeros(nslots, _np.int32),
                _np.zeros(nslots, _np.int32))

    def _precompile(self):
        """Compile the whole ladder while it is being lowered: this
        (the scheduler's) thread traces and lowers one program after
        the other, which holds the interpreter lock, and hands each
        lowering to a few threads that compile it or fetch its
        executable from the persistent compile cache and load it, which
        does not. jit keeps the lowering of a function at given argument
        types, and the lowering keeps its executable, so the warm calls
        that follow find both and only execute."""
        jobs = ([(self._prefill_prog(b), self._prefill_args(b))
                 for b in self._cfg.prefill_buckets]
                + [(self._step_prog(n), self._step_args(n))
                   for n in self._cfg.slot_buckets])
        with _futures.ThreadPoolExecutor(4) as pool:
            for done in [pool.submit(prog.lower(*args).compile)
                         for prog, args in jobs]:
                done.result()

    def _warm_prefill_spec(self, spec):
        bucket = int(spec.get("bucket", 0))
        if bucket not in self._cfg.prefill_buckets:
            return False
        pargs = self._prefill_args(bucket)
        prog = self._prefill_prog(bucket)
        if ("prefill", bucket) not in self._prog_costs:
            # roofline capture BEFORE executing: the pools are donated
            # by the call, so only the pre-call arrays are certain to
            # be live for the HLO cost pass
            self._prog_costs[("prefill", bucket)] = _health.capture_cost(
                "decode_prefill", _health.next_cost_key("dec"),
                prog, pargs,
                pkey=_pg.ProgramKey("decode_prefill", self._graph_hash,
                                    {"bucket": int(bucket),
                                     "kernel": _prefill_variant()}))
        tok0, self._k_pages, self._v_pages = _pg.warm_twice(
            prog, pargs,
            rebuild=lambda out, a: (a[0], out[1], out[2]) + a[3:])
        _np.asarray(tok0)                # block: compile + execute done

    def _warm_step_spec(self, spec):
        nslots = int(spec.get("slots", 0))
        if nslots not in self._cfg.slot_buckets:
            return False
        sargs = self._step_args(nslots)
        prog = self._step_prog(nslots)
        if ("step", nslots) not in self._prog_costs:
            self._prog_costs[("step", nslots)] = _health.capture_cost(
                "decode_step", _health.next_cost_key("dec"),
                prog, sargs,
                pkey=_pg.ProgramKey("decode_step", self._graph_hash,
                                    {"slots": int(nslots)}))
        toks, self._k_pages, self._v_pages = _pg.warm_twice(
            prog, sargs,
            rebuild=lambda out, a: (a[0], out[1], out[2]) + a[3:])
        _np.asarray(toks)

    @property
    def ready(self):
        """Warmed AND the scheduler thread is alive (the /healthz
        gate, mirroring InferenceEngine.ready)."""
        return (self._ready and self._worker is not None
                and self._worker.is_alive())

    @property
    def config(self):
        return self._cfg

    def program_count(self):
        """Compiled decode-path programs held (the compile-cache bound:
        <= len(prefill_buckets) + len(slot_buckets))."""
        return len(self._prefill_progs) + len(self._step_progs)

    @property
    def warm_report(self):
        """The last warmup's prewarm report (replayed/compile/disk-hit
        counts and wall), or None before the first warmup."""
        return self._warm_report

    def pause(self, drain=True, timeout=30.0):
        """Stop admission; with ``drain`` wait for every live and
        queued session to finish (what ModelRegistry.swap does before a
        weight hot-swap). Returns True when fully drained."""
        with self._cond:
            self._accepting = False
            self._cond.notify_all()
        if not drain:
            return self._idle()
        import time
        t_end = _tm.monotonic() + timeout
        while not self._idle() and _tm.monotonic() < t_end:
            time.sleep(0.005)
        return self._idle()

    def resume(self):
        """Re-open admission after :meth:`pause`."""
        with self._cond:
            if self._closing:
                raise EngineClosedError("engine is closed")
            self._accepting = True
            self._cond.notify_all()

    def swap_params(self, params, timeout=30.0):
        """Hot-swap the transformer weights: drains every decode
        session (they finish on the old weights), swaps the param
        pytree, re-opens admission. Zero recompiles — params are traced
        arguments of the compiled programs, not baked-in constants."""
        if not self.pause(drain=True, timeout=timeout):
            self.resume()
            raise MXNetError(
                "decode sessions did not drain within %.1fs; weights "
                "unchanged" % timeout)
        self._params = params
        self.resume()
        return self

    def _idle(self):
        with self._cond:
            return not self._live and not self._waiting

    def close(self, drain=True, timeout=30.0):
        """Stop admission; with ``drain`` finish every admitted
        session, else fail them; then stop the scheduler thread."""
        with self._cond:
            self._accepting = False
            if not drain:
                for sess in list(self._waiting) + list(self._live):
                    self._release_pages(sess)
                    sess._finish(EngineClosedError("engine closed"))
                self._waiting.clear()
                del self._live[:]
            self._closing = True
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=timeout)
        self._ready = False

    # -- admission ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, timeout_ms=None,
               stop_token=None, ctx=None):
        """Admit one generation request; returns its
        :class:`DecodeSession` stream.

        ``prompt``: iterable of int token ids. ``max_new_tokens``
        defaults to (and is capped by) ``MXNET_DECODE_MAX_NEW_TOKENS``.
        Raises :class:`QueueFullError` when the waiting queue is at
        depth, and its subclass :class:`~.kv_pages.PagePoolExhausted`
        when the page pool cannot cover prompt + max_new_tokens — both
        map to HTTP 503, distinguishable by the error detail. The page
        reservation covers the request's WHOLE lifetime, so an admitted
        session can never be evicted for memory.
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("empty prompt")
        for t in prompt:
            if t < 0 or t >= self._vocab:
                raise MXNetError("prompt token %d outside the model "
                                 "vocabulary [0, %d)" % (t, self._vocab))
        max_new = (self._cfg.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        max_new = min(max_new, self._cfg.max_new_tokens)
        plen = len(prompt)
        if plen > self._cfg.prefill_buckets[-1]:
            raise MXNetError(
                "prompt of %d tokens exceeds the largest prefill "
                "bucket %d" % (plen, self._cfg.prefill_buckets[-1]))
        if plen + max_new > self._cfg.max_context:
            raise MXNetError(
                "prompt (%d) + max_new_tokens (%d) exceeds "
                "max_context=%d" % (plen, max_new, self._cfg.max_context))
        timeout = (self._cfg.default_timeout if timeout_ms is None
                   else float(timeout_ms) / 1e3)
        deadline = (_tm.monotonic() + timeout) if timeout > 0 else None
        sess = DecodeSession(prompt, max_new, stop_token, deadline,
                             ctx if ctx is not None else _tr.active())
        # pages for the whole lifetime: the prefill BUCKET (its page
        # write covers the padded prompt) and prompt+max_new positions.
        # With window layers the prefill writes by the prompt's real
        # length: pages for the positions alone, and of the window
        # pool at most a ring. A model with linear layers reserves pages
        # for its full layers' positions here, and takes its state row
        # when it leaves the queue for a slot (``_schedule``)
        ps = self._cfg.page_size
        n_pages = pages_needed(plen + max_new, ps)
        if not (self._window or self._linear_layers or self._latent):
            n_pages = max(n_pages, pages_needed(pick_bucket(
                plen, self._cfg.prefill_buckets), ps))
        with self._cond:
            if not self._accepting or self._closing:
                self._m_rejected.labels("closed").inc()
                raise EngineClosedError(
                    "decode engine is draining/closed")
            if len(self._waiting) >= self._cfg.queue_depth:
                self._m_rejected.labels("queue_depth").inc()
                raise QueueFullError(
                    "decode queue full (%d requests waiting); retry "
                    "later" % self._cfg.queue_depth)
            try:
                sess.page_ids = self._pool.alloc(n_pages)
                if self._window:
                    sess.window_page_ids = self._wpool.alloc(
                        min(n_pages, self._ring_pages))
            except PagePoolExhausted:
                self._release_pages(sess)
                self._m_rejected.labels("pages").inc()
                raise
            bt = _np.zeros(self._cfg.pages_per_seq, _np.int32)
            bt[:n_pages] = sess.page_ids
            sess.block_table = bt
            if self._window:
                ring = _np.zeros(self._ring_pages, _np.int32)
                ring[:len(sess.window_page_ids)] = sess.window_page_ids
                sess.window_block_table = ring
            self._waiting.append(sess)
            self._m_requests.inc()
            self._note_free()
            self._cond.notify_all()
        return sess

    def generate(self, prompt, max_new_tokens=None, timeout_ms=None,
                 stop_token=None):
        """Synchronous convenience: submit + wait + full token list."""
        return self.submit(prompt, max_new_tokens, timeout_ms,
                           stop_token).result()

    def cancel(self, sess, reason="cancelled"):
        """Abort a session — the backpressure release for a client that
        disconnected mid-stream (serve/http.py calls this), so dead
        sessions stop holding slots and pages until their deadline.

        A waiting session releases its pages immediately (no compute
        ever touched them). A live one is marked failed and SWEPT by
        the scheduler at the next iteration boundary: its pages may
        still be written by in-flight compute this step, so freeing
        them here could hand them to a new admission mid-write.
        Returns True when this call cancelled the session."""
        err = MXNetError("decode session cancelled: %s" % reason)
        with self._cond:
            if sess.done:
                return False
            if sess in self._waiting:
                self._waiting.remove(sess)
                self._release_pages(sess)
                sess._finish(err)
                self._note_free()
                return True
            sess._finish(err)            # scheduler sweep retires it
            self._cond.notify_all()
            return True

    # -- scheduler ---------------------------------------------------------
    def _worker_main(self):
        """Run the scheduler loop; on a crash (a bug, an injected
        ``decode.step`` fault, a device wedge) retire every live
        session — their slots free, their pages return to the pool —
        and restart the loop in place, up to the shared restart
        budget. The page pool arrays are rebuilt (donated buffers are
        in an undefined state after a mid-step failure); retirement is
        exactly what frees the crashed sessions' pages."""
        while True:
            try:
                self._loop()
                return                   # clean exit: engine closed
            except BaseException as exc:
                self._crash_recover(exc)
                with self._cond:
                    if self._closing:
                        return
                    if self._restarts_used >= self._cfg.worker_restarts:
                        import logging
                        logging.error(
                            "decode scheduler crashed (%s) with the "
                            "restart budget (%d) exhausted; decode "
                            "serving stays down", exc,
                            self._cfg.worker_restarts)
                        return
                    self._restarts_used += 1
                _tm.counter("decode/worker_restarts_total",
                            "Decode scheduler threads restarted after "
                            "a crash").inc()

    def _crash_recover(self, exc):
        err = MXNetError("decode step failed: %s" % exc)
        with self._cond:
            victims = list(self._live) + list(self._waiting)
            del self._live[:]
            self._waiting.clear()
            for sess in victims:
                self._release_pages(sess)
                self._m_preempted.inc()
                sess._finish(err)
            self._m_occupancy.set(0)
            self._note_free()
        # donated pool buffers are unusable after a mid-program crash;
        # same-shape zeros re-hit the warmed fill program (no new
        # compile)
        self._k_pages, self._v_pages = self._fresh_pools()

    def _release_pages(self, sess):
        if sess.page_ids:
            self._pool.free(sess.page_ids)
            sess.page_ids = None
        if sess.window_page_ids:
            self._wpool.free(sess.window_page_ids)
            sess.window_page_ids = None
        if sess.state_row is not None:
            self._state_rows.free([sess.state_row])
            sess.state_row = None

    def _tables(self, full, second):
        """Block tables as the programs take them: the one table, or
        with window layers the (global, window-ring) pair, or with
        linear layers the (full layers' table, state rows) pair."""
        return ((full, second) if self._window or self._linear_layers
                else full)

    def _second_table(self, nslots=None):
        """What rides beside the block table of one sequence (a prefill)
        or of ``nslots`` rows (a step), zeroed: a window model's ring(s),
        a linear model's state row(s) — row 0, the null row."""
        lead = () if nslots is None else (nslots,)
        if self._linear_layers:
            return _np.zeros(lead, _np.int32)
        return _np.zeros(lead + (self._ring_pages or 0,), _np.int32)

    def _retire_locked(self, sess, error=None):
        """Retire a session (caller holds the lock): slot freed for
        next iteration's admission, pages back to the pool."""
        if sess in self._live:
            self._live.remove(sess)
        self._release_pages(sess)
        sess._finish(error)
        self._m_occupancy.set(len(self._live))
        self._note_free()

    def set_iteration_hook(self, fn):
        """Install (or clear, with None) a callable run on the
        SCHEDULER thread at the top of every loop iteration, before
        admission — outside the engine lock, so it may block.

        This is the deterministic-testing seam (the decode analog of
        ``fault.POINTS``): a hook that parks on a semaphore turns the
        scheduler into a single-steppable machine, which is how the
        iteration-level-scheduling ordering tests assert completion
        order without sleep/race timing.  A blocking hook also blocks
        ``close()`` — clear it (and release any parked permit) before
        teardown.  Hook exceptions take the scheduler crash-recovery
        path like any other loop failure.  Not a production surface."""
        self._iter_hook = fn

    def _loop(self):
        while True:
            hook = self._iter_hook
            if hook is not None:
                hook()
            _fault.inject("decode.step")
            with self._cond:
                wreq, self._warmup_req = self._warmup_req, None
            if wreq is not None:
                try:
                    self._do_warmup()
                except BaseException as exc:
                    wreq["error"] = exc
                finally:
                    wreq["event"].set()
            with self._cond:
                while (not self._waiting and not self._live
                       and self._warmup_req is None):
                    if self._closing:
                        return
                    self._cond.wait(0.05)
                if self._warmup_req is not None:
                    continue
                carried = len(self._live)
            # the engine's own timeline: one root per pass that has
            # work, whoever's requests it serves (the per-request spans
            # below go to the traces of callers that brought a context);
            # into the span log only, so that the ring keeps the
            # requests' traces. ``live``: sequences carried over from
            # the pass before — they wait while this pass admits and
            # prefills
            with _tr.start_span("decode.iteration", ring=False,
                                attrs={"live": carried}):
                for sess in self._schedule():
                    self._prefill(sess)
                self._step()

    def _schedule(self):
        """Expire, sweep and admit for one iteration; returns the
        sessions admitted (each is owed a prefill)."""
        with self._cond:
            t_sched0 = _tm.monotonic()
            evictions = self._expire_locked()
            admits = []
            while (self._waiting
                   and len(self._live) < self._cfg.slots):
                sess = self._waiting.popleft()
                if self._linear_layers:
                    # its state row, for its life in a slot: there is a
                    # row a slot, so a free slot has a free row
                    assert self._state_rows.free_pages, \
                        "a free slot without a free state row"
                    sess.state_row, = self._state_rows.alloc(1)
                # joins the slot list BEFORE its prefill runs (so a
                # concurrent close/crash-recover can't lose it);
                # t_admit is None until the prefill lands, which
                # keeps it out of this iteration's step batch
                self._live.append(sess)
                admits.append(sess)
            self._m_occupancy.set(len(self._live))
            t_sched1 = _tm.monotonic()
        if admits or evictions:
            self._record_schedule(admits, evictions, t_sched0, t_sched1)
        return admits

    def _expire_locked(self):
        """Fail sessions past their deadline (queued: before a prefill
        is wasted on them; live: the slot frees this iteration) and
        sweep cancelled live sessions whose pages were kept until
        in-flight compute landed. Returns the number evicted."""
        now = _tm.monotonic()
        evicted = 0
        for sess in [s for s in self._live if s.done]:
            # cancelled mid-decode: no compute is in flight between
            # iterations, so the deferred page release is safe now
            self._live.remove(sess)
            self._release_pages(sess)
            self._m_preempted.inc()
            evicted += 1
        self._m_occupancy.set(len(self._live))
        self._note_free()
        for sess in [s for s in self._waiting
                     if s.deadline is not None and now > s.deadline]:
            self._waiting.remove(sess)
            self._release_pages(sess)
            self._m_timeouts.inc()
            evicted += 1
            sess._finish(DeadlineExceededError(
                "deadline expired after %.0f ms in the decode queue"
                % ((now - sess.t_enq) * 1e3)))
        for sess in [s for s in self._live
                     if s.deadline is not None and now > s.deadline]:
            self._m_timeouts.inc()
            self._m_preempted.inc()
            evicted += 1
            self._retire_locked(sess, DeadlineExceededError(
                "deadline expired after %d of %d tokens"
                % (sess.generated, sess.max_new_tokens)))
        return evicted

    def _record_schedule(self, admits, evictions, t0, t1):
        sid = None
        attrs = {"slots": len(self._live),
                 "live_pages": self._pool.used_pages,
                 "evictions": evictions}
        for sess in admits:
            ctx = sess.tctx
            if ctx is None or not ctx.sampled:
                continue
            if sid is None:
                sid = _tr.new_span_id()
            _tr.record_span("decode.schedule", ctx, t0, t1,
                            span_id=sid, parent_id=ctx.span_id,
                            attrs=attrs)

    def _prefill(self, sess):
        """Bucketed prefill for one admission: pad the prompt to its
        power-of-two ladder bucket, run ONE batched causal forward
        that writes the prompt K/V into the session's pages, and emit
        the first generated token from the logits at the last real
        position."""
        bucket = pick_bucket(sess.prompt_len, self._cfg.prefill_buckets)
        n_pb = bucket // self._cfg.page_size
        with self._cond:
            if sess.done:                # failed concurrently (close/
                return                   # cancel/deadline) pre-prefill
            # snapshot under the lock: a concurrent close may null
            # page_ids the instant the session is failed
            if self._window:
                page_ids = (sess.block_table[:n_pb],
                            sess.window_block_table)
            elif self._linear_layers:
                page_ids = (sess.block_table[:n_pb],
                            _np.asarray(sess.state_row, _np.int32))
            elif self._latent:       # the pages past the request's own
                page_ids = sess.block_table[:n_pb].copy()    # are null
            else:
                page_ids = _np.asarray(sess.page_ids[:n_pb], _np.int32)
        padded = _np.zeros((1, bucket), _np.int32)
        padded[0, :sess.prompt_len] = sess.prompt
        with _tr.child_span("decode.prefill") as span:
            if self._latent:
                # latents this prefill writes: a token a layer
                span.set_attr("latent_context_tokens",
                              sess.prompt_len * self._model_cfg.n_layers)
            if self._linear_layers:
                # tokens the chunked rule runs over: the REAL prompt a
                # linear layer (a bucket's padding is no work)
                span.set_attr("linear_tokens",
                              sess.prompt_len * self._linear_layers)
            t0 = _tm.monotonic()
            out, self._k_pages, self._v_pages = self._prefill_prog(bucket)(
                self._params, self._k_pages, self._v_pages, page_ids,
                padded, _np.array([sess.prompt_len], _np.int32))
            out = _np.asarray(out).reshape(-1)
            tok0 = int(out[0])
            t1 = _tm.monotonic()
            if self._moe:
                experts = self._note_moe(span, out[1:], bucket,
                                         sess.prompt_len)
                sess.expert_choices.append(experts[:, :sess.prompt_len])
        self._m_prefill.observe(
            t1 - t0, trace_id=sess.tctx.trace_id if sess.tctx else None)
        _health.note_decode("prefill", bucket, t1 - t0,
                            self._prog_costs.get(("prefill", bucket)))
        if sess.tctx is not None and sess.tctx.sampled:
            _tr.record_span("decode.prefill", sess.tctx, t0, t1,
                            parent_id=sess.tctx.span_id,
                            attrs={"bucket": bucket,
                                   "prompt_len": sess.prompt_len})
        with self._cond:
            if sess.done:
                return
            sess.t_admit = t0
            sess.pos = sess.prompt_len
            self._emit_locked(sess, tok0)

    def _note_moe(self, span, stats, width, real):
        """What a call's expert layers did, from the numbers its program
        returned after the tokens (``stats``: per expert layer the count
        of held experts that received a row, then the experts chosen for
        each of the ``width`` rows the program ran): counts the
        assignments of the ``real`` rows (padding and dummy slots are no
        work), those of them that landed on an expert held here — the
        rows the grouped product computed — and the experts touched, puts
        them on the call's span, and returns the choices as ``(layers,
        width, top_k)``."""
        layers, k = self._moe_layers, self._model_cfg.moe_top_k
        experts = stats[layers:].reshape(layers, k, width).transpose(
            0, 2, 1).astype(_np.int16)
        chosen = experts[:, :real]
        n_assigned = real * k * layers
        n_rows = int(_np.count_nonzero(
            (chosen >= self._moe_first) & (chosen < self._moe_end)))
        n_active = int(stats[:layers].sum())
        self._m_moe_rows.inc(n_assigned)
        self._m_moe_absent.inc(n_assigned - n_rows)
        self._m_moe_active.inc(n_active)
        span.set_attr("moe_assignments", n_assigned)
        span.set_attr("moe_rows", n_rows)
        span.set_attr("moe_active_experts", n_active)
        return experts

    def _emit_locked(self, sess, tok):
        """Deliver one token; retire the session once it hits its
        max_new_tokens budget or its stop token (caller holds the
        lock — retirement mutates the slot list)."""
        sess._emit(tok)
        self._m_tokens.inc()
        if (sess.generated >= sess.max_new_tokens
                or (sess.stop_token is not None
                    and tok == sess.stop_token)):
            self._retire_locked(sess)

    def _step(self):
        """One decode iteration: every live slot advances one token
        through the slot-bucket program (dummy slots write the null
        page and are discarded)."""
        with self._cond:
            live = [s for s in self._live if s.t_admit is not None]
        if not live:
            return
        nslots = pick_bucket(len(live), self._cfg.slot_buckets)
        tokens = _np.zeros(nslots, _np.int32)
        pos = _np.zeros(nslots, _np.int32)
        bt = _np.zeros((nslots, self._cfg.pages_per_seq), _np.int32)
        second = self._second_table(nslots)
        for i, sess in enumerate(live):
            tokens[i] = sess.last_token
            pos[i] = sess.pos
            bt[i] = sess.block_table
            if self._window:
                second[i] = sess.window_block_table
            elif self._linear_layers:
                # a session released since the snapshot (a concurrent
                # cancel or close) steps on the null row
                second[i] = sess.state_row or 0
        # context_tokens: the positions this step attends over, the
        # new token's own included — the attention kernel's work in a
        # global layer; window_context_tokens: the same in a window
        # layer, which sees at most its window of each row
        context = pos[:len(live)] + 1
        attrs = {"context_tokens": int(context.sum()),
                 "window_context_tokens": int(
                     _np.minimum(context, self._window).sum()
                     if self._window else context.sum())}
        if self._latent:
            # cached vectors the step's absorbed attends read, and the
            # queries they are read for, all layers
            attrs["latent_context_tokens"] = int(
                context.sum()) * self._model_cfg.n_layers
            attrs["latent_rows"] = len(live) * self._model_cfg.n_layers
        if self._linear_layers:
            # states the recurrent rule reads and writes: a REAL row a
            # linear layer (dummy slots are no work); context_tokens is
            # then the full layers' K/V alone
            attrs["linear_rows"] = len(live) * self._linear_layers
        with _tr.child_span("decode.step", attrs=attrs) as span:
            t0 = _tm.monotonic()
            toks, self._k_pages, self._v_pages = self._step_prog(nslots)(
                self._params, self._k_pages, self._v_pages,
                self._tables(bt, second), tokens, pos)
            toks = _np.asarray(toks)
            t1 = _tm.monotonic()
            if self._moe:
                experts = self._note_moe(span, toks[nslots:], nslots,
                                         len(live))
                for i, sess in enumerate(live):
                    sess.expert_choices.append(experts[:, i:i + 1])
        self._m_step.observe(t1 - t0)
        _health.note_decode("step", nslots, t1 - t0,
                            self._prog_costs.get(("step", nslots)))

        traced = [s for s in live
                  if s.tctx is not None and s.tctx.sampled]
        if traced:
            sid = _tr.new_span_id()
            attrs = {"slots": len(live), "bucket": nslots,
                     "live_pages": self._pool.used_pages}
            for sess in traced:
                _tr.record_span("decode.step", sess.tctx, t0, t1,
                                span_id=sid,
                                parent_id=sess.tctx.span_id,
                                attrs=attrs)
        with self._cond:
            for i, sess in enumerate(live):
                if sess.done:            # expired/retired concurrently
                    continue
                sess.pos += 1
                self._emit_locked(sess, int(toks[i]))

    # -- compiled programs -------------------------------------------------
    # both builders route through the process-wide compiled-program
    # registry: engines over the same architecture/page layout share
    # one program per bucket (weights are traced arguments), and the
    # registry's warm-set entry + persistent cache make a fresh
    # replica's warmup a disk load

    def _prefill_prog(self, bucket):
        prog = self._prefill_progs.get(bucket)
        if prog is None:
            def build():
                import jax
                import jax.numpy as jnp
                from ..parallel.transformer import (
                    cache_pools, paged_cache, transformer_prefill_paged)
                cfg, ps, moe = (self._model_cfg, self._cfg.page_size,
                                self._moe)

                @functools.partial(jax.jit, donate_argnums=(1, 2))
                def prog(params, k_pages, v_pages, page_ids, tokens,
                         length):
                    paged = paged_cache(
                        k_pages, v_pages, jax.tree_util.tree_map(
                            lambda t: t[None], page_ids), ps, cfg)
                    logits, paged, stats = transformer_prefill_paged(
                        params, paged, tokens, length, cfg,
                        with_stats=True)
                    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
                    # what the expert layers did rides the token's own
                    # fetch: (1 + layers + layers * k * bucket,) int32
                    out = (jnp.concatenate(
                        [tok0, stats["moe_active_experts"],
                         stats["moe_experts"].reshape(-1)]) if moe
                        else tok0[0])
                    return (out,) + cache_pools(paged)

                return prog

            prog = _pg.get_or_build(
                _pg.ProgramKey("decode_prefill", self._graph_hash,
                               {"bucket": int(bucket),
                                "kernel": _prefill_variant()}), build)
            self._prefill_progs[bucket] = prog
        return prog

    def _step_prog(self, nslots):
        prog = self._step_progs.get(nslots)
        if prog is None:
            def build():
                import jax
                import jax.numpy as jnp
                from ..parallel.transformer import (
                    cache_pools, paged_cache, transformer_decode_step)
                cfg, ps, moe = (self._model_cfg, self._cfg.page_size,
                                self._moe)

                @functools.partial(jax.jit, donate_argnums=(1, 2))
                def prog(params, k_pages, v_pages, block_tables, tokens,
                         pos):
                    paged = paged_cache(k_pages, v_pages, block_tables,
                                        ps, cfg)
                    logits, paged, stats = transformer_decode_step(
                        params, paged, tokens, pos, cfg, with_stats=True)
                    out = jnp.argmax(logits, -1).astype(jnp.int32)
                    if moe:         # ... + layers * (1 + k * slots)
                        out = jnp.concatenate(
                            [out, stats["moe_active_experts"],
                             stats["moe_experts"].reshape(-1)])
                    return (out,) + cache_pools(paged)

                return prog

            prog = _pg.get_or_build(
                _pg.ProgramKey("decode_step", self._graph_hash,
                               {"slots": int(nslots)}), build)
            self._step_progs[nslots] = prog
        return prog
