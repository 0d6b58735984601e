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
  its prefills and its step under it, context or none;
* ONE STEP IN FLIGHT: while the next step's rows are exactly the rows
  of the step on the device, the loop dispatches it with that step's
  token array still on the device and only then fetches and delivers
  the tokens, so the dispatch and the fetch lie behind the device's
  work. It holds the dispatch until the step in flight is due to end
  (less a lead a dispatch needs), so that a request arriving meanwhile
  is prefilled right behind that step, not behind one more; any change
  of the rows drains the pipeline into one step from host arrays.

Decoding is greedy (argmax) — deliberately: the acceptance contract is
that batched continuous decode is BITWISE-identical to per-request
unbatched :func:`~mxnet_tpu.parallel.transformer.transformer_decode_step`
decode, and tests/test_decode_serve.py asserts it token-for-token.

Telemetry: ``decode/tokens_total``, ``decode/slot_occupancy``,
``decode/page_pool_free``, ``decode/prefill_seconds`` /
``decode/step_seconds``, ``decode/steps_ahead_total``,
``decode/preempted_total``, ``decode/timeouts_total``,
``decode/worker_restarts_total``.
Knobs: ``MXNET_DECODE_*`` (config.py). Docs: docs/decode_serving.md.
"""
from __future__ import annotations

import concurrent.futures as _futures
import functools
import logging
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

# a turn of the scheduler's loop this long, whose program calls' fetches
# cover less than half of it, logs one warning with its split
_STALL_S = 1.0

# a step that goes ahead is dispatched a LEAD before the step in flight is
# due to end. The lead covers the dispatch, the hold's wake-up and the time
# a fetch takes to see a step end; the loop learns it from what it sees:
# it grows by _LEAD_UP whenever a step found the step before it already
# landed (the device idled), and shrinks by _LEAD_DOWN whenever it did not
# — so that about one step in twenty-five finds the device idle — never
# under _LEAD_MIN_S nor over a step
_LEAD_MIN_S = 0.2e-3
_LEAD_UP, _LEAD_DOWN = 1.25, 0.99
# weight of a new reading in the step's device time (an exponential mean)
_EWMA = 0.25

_SENTINEL = object()


class _Attrs(dict):
    """The attributes of a span recorded after the fact, gathered as a
    live span's are (``set_attr``)."""

    __slots__ = ()

    def set_attr(self, key, value):
        self[key] = value
        return self


class _Step(object):
    """One decode step's program call, from before its dispatch until its
    tokens are delivered: its rows (live sessions in slot order) and the
    host arrays it was called with, which the step after it reuses when
    it goes ahead; its two outputs (``feed``: the tokens as the next step
    takes them, on the device; ``out``: what is fetched, the tokens and
    behind them what the expert layers did); the ``decode.iteration``
    that dispatched it (``parent``), its span's attributes, and its
    instants (``end``: when its device work is due to end)."""

    __slots__ = ("rows", "parent", "nslots", "tables", "pos", "feed", "out",
                 "attrs", "ahead", "t0", "t_dispatched", "end")

    def __init__(self, rows, parent):
        self.rows = rows
        self.parent = parent
        self.feed = self.out = None
        self.ahead = False
        self.end = 0.0


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
        # a model with recurrent (linear-attention or Mamba) layers keeps
        # another kind of state beside its attention layers' pages: a
        # fixed-size STATE ROW a live session, in pools of their own
        # (transformer.LinearStateCache). A session in a slot holds one
        # and a queued one none, so there is a row a slot and row 0, the
        # null row of a step bucket's dummy slots: nothing to configure
        self._linear_layers = kinds.count("linear")
        self._mamba_layers = kinds.count("mamba")
        self._state_layers = self._linear_layers + self._mamba_layers
        self._state_rows = (PagePool(self._cfg.slots + 1, kind="state")
                            if self._state_layers else None)
        # a cross-decoder's layers cache nothing: the cross-attention
        # layers read the pages of the full layer before them, which with
        # it makes the readers of that pool; a prefill runs the
        # cross-decoder on the prompt's last position alone
        self._shared_readers = (kinds.count("cross") + 1
                                if "cross" in kinds else 0)
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
        # (t0, t_dispatched, t_fetched) of this pass's program calls
        self._calls = []
        # decode steps dispatched and not yet delivered, oldest first (one
        # between passes, two while one goes ahead of the other); the
        # session whose prefill is in flight; sessions retired while one
        # of those still writes their pages, which go back when it lands
        self._flight = []
        self._prefilling = None
        self._held = []
        # why the last step that did not go ahead could not (drained_for)
        self._why = "idle"
        # what the loop has seen: the device time of a step by slot
        # bucket, the lead, and when a program's output last reached the
        # host (and whether its fetch waited for the device)
        self._step_s = {}
        self._lead_s = _LEAD_MIN_S
        self._t_landed = 0.0
        self._landed_waited = False

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
            "Prefill wall time per admission (bucketed prompt forward): "
            "its output's fetch less the later of its dispatch and the "
            "fetch of the output before it")
        self._m_step = _tm.histogram(
            "decode/step_seconds",
            "Decode step wall time (one token for every live slot): its "
            "tokens' fetch less the later of its dispatch and the fetch "
            "of the output before it")
        self._m_ahead = _tm.counter(
            "decode/steps_ahead_total",
            "Decode steps dispatched while the step before them was still "
            "running on the device (with its token array as their input)")
        self._m_preempted = _tm.counter(
            "decode/preempted_total",
            "Sessions retired abnormally mid-decode (crash containment "
            "or deadline expiry in a slot)")
        self._m_timeouts = _tm.counter(
            "decode/timeouts_total",
            "Sessions failed on deadline expiry (queued or decoding)")
        self._note_free()

    def _pool_sizes(self):
        """``num_pages`` as ``init_kv_pages`` takes it: the pages of the
        global pool, paired with the window pool's where the model has
        window layers, and that with the state rows where it has
        recurrent layers."""
        pages = self._cfg.num_pages
        if self._window:
            pages = (pages, self._cfg.window_pages)
        if self._state_layers:
            pages = (pages, self._state_rows.num_pages)
        return pages

    def _fresh_pools(self):
        """Zeroed pools, committed to their device: a program's outputs
        are, so that a call on fresh pools keys the executable every
        later call does (the buffers are not copied)."""
        import jax
        from ..parallel.transformer import init_kv_pages
        pools = init_kv_pages(self._model_cfg, self._pool_sizes(),
                              self._cfg.page_size)
        self._dev = next(iter(jax.tree_util.tree_leaves(pools)[0].devices()))
        return jax.device_put(pools, self._dev)

    def _note_free(self):
        self._m_free.set(self._pool.free_pages)
        self._m_pages_free.labels("global").set(self._pool.free_pages)
        if self._latent:
            self._m_pages_free.labels("latent").set(self._pool.free_pages)
        if self._wpool is not None:
            self._m_pages_free.labels("window").set(
                self._wpool.free_pages)
        if self._state_layers:
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
                             self._ring_table(), _np.zeros((), _np.int32)),
                _np.zeros((1, bucket), _np.int32),
                _np.array([bucket], _np.int32))

    def _step_args(self, nslots):
        return (self._params, self._k_pages, self._v_pages,
                self._tables(
                    _np.zeros((nslots, self._cfg.pages_per_seq), _np.int32),
                    self._ring_table(nslots),
                    _np.zeros(nslots, _np.int32)),
                self._on_device(_np.zeros(nslots, _np.int32)),
                _np.zeros(nslots, _np.int32))

    def _on_device(self, tokens):
        """A step's tokens from the host, committed to the pools' device:
        the form of the token array a step that goes ahead takes from the
        step before it, so that both call ONE executable (a host array
        would key another)."""
        import jax
        return jax.device_put(tokens, self._dev)

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
        feed, out, self._k_pages, self._v_pages = _pg.warm_twice(
            prog, sargs,
            rebuild=lambda out, a: (a[0], out[2], out[3]) + a[3:])
        _np.asarray(out)
        # as a step that goes ahead runs: its tokens straight out of the
        # step before it, each call queued behind the one before; timed
        # as the loop sees such steps, fetch to fetch
        tables, pos = sargs[3], sargs[5]
        calls = []
        for _ in range(3):
            t0 = _tm.monotonic()
            feed, out, self._k_pages, self._v_pages = prog(
                self._params, self._k_pages, self._v_pages, tables, feed,
                pos)
            calls.append((t0, _tm.monotonic(), out))
        fetched = []
        for _t0, _td, out in calls:
            _np.asarray(out)
            fetched.append(_tm.monotonic())
        self._step_s[nslots] = ((fetched[-1] - fetched[0])
                                / (len(fetched) - 1))
        # the lead starts at twice what such a dispatch took
        self._lead_s = max(self._lead_s, 2.0 * sorted(
            td - t0 for t0, td, _out in calls)[1])

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
        # pool at most a ring. A model with recurrent layers reserves
        # pages for its attention layers' positions here, and takes its
        # state row when it leaves the queue for a slot (``_schedule``)
        ps = self._cfg.page_size
        n_pages = pages_needed(plen + max_new, ps)
        if not (self._window or self._state_layers or self._latent):
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
        the scheduler at the next iteration boundary, and its pages go
        back once the steps in flight that write them have landed:
        freeing them here could hand them to a new admission mid-write.
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
        # a call still in flight ends before its pages go back and its
        # pools are dropped (the pools are the last call's outputs, so
        # they wait for every call before it); one that failed has none
        try:
            import jax
            jax.block_until_ready((self._k_pages, self._v_pages))
        except Exception:
            pass
        with self._cond:
            del self._flight[:]
            self._prefilling = None
            self._release_held_locked()
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
        """Give a retired session's pages and state row back (caller holds
        the lock), or, while a call in flight still writes them, keep
        them until it has landed (``_release_held_locked``)."""
        if (sess is self._prefilling
                or any(sess in c.rows for c in self._flight)):
            if sess not in self._held:
                self._held.append(sess)
            return
        self._free_pages(sess)

    def _release_held_locked(self):
        if not self._held:
            return
        writing = set(s for c in self._flight for s in c.rows)
        writing.add(self._prefilling)
        still = [s for s in self._held if s in writing]
        for sess in self._held:
            if sess not in writing:
                self._free_pages(sess)
        self._held = still
        self._note_free()

    def _free_pages(self, sess):
        if sess.page_ids:
            self._pool.free(sess.page_ids)
            sess.page_ids = None
        if sess.window_page_ids:
            self._wpool.free(sess.window_page_ids)
            sess.window_page_ids = None
        if sess.state_row is not None:
            self._state_rows.free([sess.state_row])
            sess.state_row = None

    def _tables(self, full, ring, rows=None):
        """Block tables as the programs take them: the one table, or
        with window layers the (global, window-ring) pair; with
        recurrent layers that paired with the state row(s)."""
        tables = (full, ring) if self._window else full
        return (tables, rows) if self._state_layers else tables

    def _ring_table(self, nslots=None):
        """A window model's ring of one sequence (a prefill) or of
        ``nslots`` rows (a step), zeroed: the null page."""
        lead = () if nslots is None else (nslots,)
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
            t_top = _tm.monotonic()
            hook = self._iter_hook
            if hook is not None:
                self._land_all()         # the hook sees every token
                hook()
            _fault.inject("decode.step")
            with self._cond:
                wreq, self._warmup_req = self._warmup_req, None
            if wreq is not None:
                self._land_all()
                try:
                    self._do_warmup()
                except BaseException as exc:
                    wreq["error"] = exc
                finally:
                    wreq["event"].set()
                t_top = _tm.monotonic()      # a warm-up is no stall
            t_wait0 = None
            with self._cond:
                while (not self._waiting and not self._live
                       and not self._flight and self._warmup_req is None):
                    if self._closing:
                        return
                    if t_wait0 is None:
                        t_wait0 = _tm.monotonic()
                    self._cond.wait(0.05)
                if self._warmup_req is not None:
                    continue
                carried = len(self._live)
            waited = 0.0 if t_wait0 is None else _tm.monotonic() - t_wait0
            # the engine's own timeline: one root per pass that has
            # work, whoever's requests it serves (the per-request spans
            # below go to the traces of callers that brought a context);
            # into the span log only, so that the ring keeps the
            # requests' traces. ``live``: sequences carried over from
            # the pass before — they wait while this pass admits and
            # prefills; ``t_wait0``: when the loop began to wait for
            # work, on a pass that had to; ``t_held``: when the pass's hold
            # for the step in flight ended, on a pass that held
            attrs = {"live": carried}
            if t_wait0 is not None:
                attrs["t_wait0"] = t_wait0
            t_pass0 = _tm.monotonic()
            self._calls = []
            with _tr.start_span("decode.iteration", ring=False,
                                attrs=attrs) as it:
                self._pass(it)
            self._check_stall(t_top, waited, t_pass0, _tm.monotonic())

    def _pass(self, it):
        """One pass: the step in flight is followed by the next step of
        the same rows, dispatched before its own tokens are fetched (it
        goes AHEAD); or the loop drains — expiry and admission, each
        admission's prefill queued right behind the step in flight, that
        step's tokens, then a step of the rows live now from host arrays."""
        why = self._why
        if self._flight:
            why = self._hold(it)
            if why is None:
                self._go_ahead(it)
                return
        admits = self._schedule()
        for sess in admits:
            self._prefill(sess)
        self._land_all()
        self._step(it, "admit" if admits else why)

    def _lead(self):
        """How long before the step in flight is due to end the next one
        is dispatched (a method: a test holds the loop by replacing it)."""
        return self._lead_s

    def _hold(self, it):
        """Wait until the step in flight is due to end less the lead a
        dispatch needs, unless something changes the next step's rows
        first (``submit``, ``cancel`` and ``close`` wake the loop); returns
        why the next step cannot go ahead, or None."""
        rec = self._flight[-1]
        held = False
        with self._cond:
            why = self._cannot_go_ahead(rec)
            while why is None:
                left = rec.end - self._lead() - _tm.monotonic()
                if left <= 0:
                    break
                held = True
                self._cond.wait(left)
                why = self._cannot_go_ahead(rec)
        if held:
            it.set_attr("t_held", _tm.monotonic())
        return why

    def _cannot_go_ahead(self, rec):
        """Why the step after ``rec`` (in flight, its tokens undelivered)
        cannot have exactly its rows, or None (caller holds the lock)."""
        if self._iter_hook is not None:
            return "hook"
        if self._warmup_req is not None:
            return "warmup"
        now = _tm.monotonic()
        for sess in rec.rows:
            if sess.done:
                return "retire" if sess.error is None else "cancel"
            if sess.generated + 2 > sess.max_new_tokens:
                return "retire"
            if sess.deadline is not None and now > sess.deadline:
                return "expire"
        if self._waiting:
            if len(self._live) + len(self._held) < self._cfg.slots:
                return "admit"
            if any(s.deadline is not None and now > s.deadline
                   for s in self._waiting):
                return "expire"
        return None

    def _settle(self, rec):
        """After ``rec``'s dispatch (and the landing of the step before
        it): when its successor is already known not to go ahead for a
        reason other than an admission, land it now — the next pass would
        only wait for it. An admission's prefill is queued behind it."""
        with self._cond:
            why = self._cannot_go_ahead(rec)
        if why is not None and why != "admit":
            self._land(rec)
            self._why = why

    def _go_ahead(self, it):
        """Dispatch the step after the one in flight with its rows, their
        tables, one position on and its token array as the tokens (a
        bucket's dummy slots stay at position 0), then land the one in
        flight."""
        prev = self._flight[-1]
        rec = self._dispatch(it, prev.rows, prev.nslots, prev.tables,
                             prev.feed, prev.pos + (prev.pos > 0), prev)
        self._land(prev)
        rec.end = (self._t_landed if rec.ahead
                   else rec.t_dispatched) + self._step_s[rec.nslots]
        self._settle(rec)

    def _land_all(self):
        while self._flight:
            self._land(self._flight[0])

    def _note_call(self, span, t0, t_dispatched, t_fetched):
        """A program call's instants: the call returned (arguments, the
        tables' H2D and the enqueue done) and its output reached the host.
        On the call's span for the log's readers, and kept for the pass's
        stall line."""
        span.set_attr("t_dispatched", t_dispatched)
        span.set_attr("t_fetched", t_fetched)
        self._calls.append((t0, t_dispatched, t_fetched))

    def _check_stall(self, t_top, waited, t_pass0, t_end):
        """One warning for a turn of the loop (from its top, its wait for
        work left out) over ``_STALL_S`` whose calls' dispatch..fetch
        cover less than half of it: where the turn went, each state less
        the ``python.gc`` collections inside it, and those collections —
        so that an untraced run that stood still names the cause in its
        own log."""
        turn = t_end - t_top - waited
        if turn <= _STALL_S:
            return
        calls = self._calls
        fetch = sum(f - d for _c, d, f in calls)
        if fetch >= turn / 2:
            return
        gcs = [r for r in _tr.span_log() if r["name"] == "python.gc"
               and r["t1"] > t_top and r["t0"] < t_end]

        def gc_in(a, b):
            return sum(max(0.0, min(b, r["t1"]) - max(a, r["t0"]))
                       for r in gcs)

        dispatch = sum(d - c - gc_in(c, d) for c, d, _f in calls)
        fetch -= sum(gc_in(d, f) for _c, d, f in calls)
        in_calls = sum(gc_in(c, f) for c, _d, f in calls)
        host = (t_end - t_pass0 - sum(f - c for c, _d, f in calls)
                - (gc_in(t_pass0, t_end) - in_calls))
        top = t_pass0 - t_top - waited - gc_in(t_top, t_pass0)
        logging.getLogger(__name__).warning(
            "decode loop stalled: a turn of %.3f s (its wait for work left "
            "out) with %d program call(s); python.gc %.3f s, dispatch %.3f, "
            "fetch %.3f, host %.3f, the loop's top %.3f; python.gc "
            "collections inside: %s", turn, len(calls),
            gc_in(t_top, t_end), dispatch, fetch, host, top,
            ", ".join("generation %d %.3f s (%d collected, thread %s)"
                      % (r["attrs"]["generation"], r["t1"] - r["t0"],
                         r["attrs"]["collected"], r["attrs"]["thread"])
                      for r in gcs)
            or ("none" if _tr.enabled() else "not logged, tracing is off"))

    def _schedule(self):
        """Expire, sweep and admit for one iteration; returns the
        sessions admitted (each is owed a prefill)."""
        with self._cond:
            t_sched0 = _tm.monotonic()
            evictions = self._expire_locked()
            admits = []
            # a retired session whose pages a step in flight still writes
            # keeps its slot (and its state row) until that step lands
            while (self._waiting and len(self._live) + len(self._held)
                   < self._cfg.slots):
                sess = self._waiting.popleft()
                if self._state_layers:
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
        sweep cancelled live sessions. Returns the number evicted."""
        now = _tm.monotonic()
        evicted = 0
        for sess in [s for s in self._live if s.done]:
            # cancelled mid-decode: its pages go back once no step in
            # flight writes them (_release_pages)
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
        position. A step in flight lands behind the prefill's dispatch:
        the device runs the prefill right after that step."""
        bucket = pick_bucket(sess.prompt_len, self._cfg.prefill_buckets)
        n_pb = bucket // self._cfg.page_size
        with self._cond:
            if sess.done:                # failed concurrently (close/
                return                   # cancel/deadline) pre-prefill
            self._prefilling = sess
            # snapshot under the lock: a concurrent close may null
            # page_ids the instant the session is failed
            if self._window or self._state_layers:
                page_ids = self._tables(
                    sess.block_table[:n_pb], sess.window_block_table,
                    _np.asarray(sess.state_row or 0, _np.int32))
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
            if self._mamba_layers:
                # ... and the selective scan, a Mamba layer
                span.set_attr("mamba_tokens",
                              sess.prompt_len * self._mamba_layers)
            if self._shared_readers:
                # the prompt's tokens that ran the self-decoder alone, and
                # those that ran the cross-decoder too (the last one)
                span.set_attr("self_decoder_only_tokens",
                              sess.prompt_len - 1)
                span.set_attr("cross_decoder_tokens", 1)
            t0 = _tm.monotonic()
            out, self._k_pages, self._v_pages = self._prefill_prog(bucket)(
                self._params, self._k_pages, self._v_pages, page_ids,
                padded, _np.array([sess.prompt_len], _np.int32))
            t_dispatched = _tm.monotonic()
            self._land_all()
            out = _np.asarray(out).reshape(-1)
            tok0 = int(out[0])
            t1 = _tm.monotonic()
            self._note_call(span, t0, t_dispatched, t1)
            if self._moe:
                experts = self._note_moe(span, out[1:], bucket,
                                         sess.prompt_len)
                sess.expert_choices.append(experts[:, :sess.prompt_len])
        # the time the prefill added to the loop (the step it was queued
        # behind landed first)
        added = t1 - max(t0, self._t_landed)
        self._t_landed = t1
        self._m_prefill.observe(
            added, trace_id=sess.tctx.trace_id if sess.tctx else None)
        _health.note_decode("prefill", bucket, added,
                            self._prog_costs.get(("prefill", bucket)))
        if sess.tctx is not None and sess.tctx.sampled:
            _tr.record_span("decode.prefill", sess.tctx, t0, t1,
                            parent_id=sess.tctx.span_id,
                            attrs={"bucket": bucket,
                                   "prompt_len": sess.prompt_len})
        with self._cond:
            self._prefilling = None
            self._release_held_locked()
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

    def _step(self, it, why):
        """A decode step of every live slot from host arrays (dummy slots
        write the null page and are discarded), left in flight. ``why``:
        why it does not go ahead of a step before it."""
        with self._cond:
            live = [s for s in self._live
                    if s.t_admit is not None and not s.done]
        if not live:
            return
        nslots = pick_bucket(len(live), self._cfg.slot_buckets)
        tokens = _np.zeros(nslots, _np.int32)
        pos = _np.zeros(nslots, _np.int32)
        bt = _np.zeros((nslots, self._cfg.pages_per_seq), _np.int32)
        ring = self._ring_table(nslots)
        rows = _np.zeros(nslots, _np.int32)
        for i, sess in enumerate(live):
            tokens[i] = sess.last_token
            pos[i] = sess.pos
            bt[i] = sess.block_table
            if self._window:
                ring[i] = sess.window_block_table
            # a session released since the snapshot (a concurrent cancel
            # or close) steps on the null row
            rows[i] = sess.state_row or 0
        rec = self._dispatch(it, live, nslots, self._tables(bt, ring, rows),
                             self._on_device(tokens), pos, None, why)
        rec.end = rec.t_dispatched + self._step_s.get(nslots, 0.0)
        self._settle(rec)

    def _dispatch(self, it, rows, nslots, tables, tokens, pos, prev,
                  why=None):
        """Call the slot bucket's step program; returns the call, in
        flight. ``prev``: the step in flight whose token array
        ``tokens`` is, when this one goes ahead of it."""
        rec = _Step(rows, it.ctx)
        with self._cond:
            self._flight.append(rec)     # its rows' pages are written now
        rec.nslots, rec.tables, rec.pos = nslots, tables, pos
        # context_tokens: the positions this step attends over, the
        # new token's own included — the attention kernel's work in a
        # global layer; window_context_tokens: the same in a window
        # layer, which sees at most its window of each row
        context = pos[:len(rows)] + 1
        attrs = rec.attrs = _Attrs(
            context_tokens=int(context.sum()),
            window_context_tokens=int(
                _np.minimum(context, self._window).sum()
                if self._window else context.sum()))
        if self._latent:
            # cached vectors the step's absorbed attends read, and the
            # queries they are read for, all layers
            attrs["latent_context_tokens"] = int(
                context.sum()) * self._model_cfg.n_layers
            attrs["latent_rows"] = len(rows) * self._model_cfg.n_layers
        if self._linear_layers:
            # states the recurrent rule reads and writes: a REAL row a
            # linear layer (dummy slots are no work); context_tokens is
            # then the full layers' K/V alone
            attrs["linear_rows"] = len(rows) * self._linear_layers
        if self._mamba_layers:
            # the same of the Mamba layers; and the rows that hold a
            # state row (dummy slots step on the null row)
            attrs["mamba_rows"] = len(rows) * self._mamba_layers
            attrs["state_rows"] = len(rows)
        if self._shared_readers:
            # the layers whose attends read the one full layer's pages
            # (context_tokens of them each), and the live pages a paged
            # attend walks over the rows: of the full layer, and of a
            # window layer's ring (its window's pages, as the kernel
            # counts them)
            last = (context - 1) // self._cfg.page_size
            attrs["shared_kv_readers"] = self._shared_readers
            attrs["full_pages_read"] = int((last + 1).sum())
            if self._window:
                first = _np.maximum(
                    _np.maximum(context - self._window, 0)
                    // self._cfg.page_size, last - self._ring_pages + 1)
                attrs["window_pages_read"] = int((last - first + 1).sum())
        rec.t0 = _tm.monotonic()
        rec.feed, rec.out, self._k_pages, self._v_pages = \
            self._step_prog(nslots)(self._params, self._k_pages,
                                    self._v_pages, tables, tokens, pos)
        rec.t_dispatched = _tm.monotonic()
        # ahead: queued while the step before it still ran on the device
        rec.ahead = prev is not None and not prev.out.is_ready()
        attrs["ahead"] = rec.ahead
        if rec.ahead:
            self._m_ahead.inc()
        else:
            attrs["drained_for"] = why if prev is None else "late"
        if prev is not None:
            # a lead of a whole step holds nothing: no use growing past it
            self._lead_s = max(_LEAD_MIN_S, min(
                self._lead_s * (_LEAD_DOWN if rec.ahead else _LEAD_UP),
                self._step_s.get(nslots, _LEAD_MIN_S)))
        return rec

    def _land(self, rec):
        """Fetch a step's tokens and deliver them to its rows still live;
        the step is no longer in flight, and the sessions whose pages it
        was the last to write give them back. A row that ended in the
        step before (its stop token, a cancel) was dead in this one: its
        token is never emitted."""
        waited = not rec.out.is_ready()
        out = _np.asarray(rec.out)
        t_fetched = _tm.monotonic()
        # the device arrays go here, before the tokens do (a step that
        # went ahead of this one holds its token array already)
        rec.feed = rec.out = None
        nslots, rows = rec.nslots, rec.rows
        experts = None
        if self._moe:
            experts = self._note_moe(rec.attrs, out[nslots:], nslots,
                                     len(rows))
        self._note_call(rec.attrs, rec.t0, rec.t_dispatched, t_fetched)
        # the time the step added to the loop: from the later of its own
        # dispatch and the fetch of the output before it
        added = t_fetched - max(rec.t0, self._t_landed)
        self._learn(rec, waited, t_fetched)
        self._m_step.observe(added)
        _health.note_decode("step", nslots, added,
                            self._prog_costs.get(("step", nslots)))
        t1 = _tm.monotonic()
        # recorded explicitly: the span may end in the pass after the one
        # that dispatched it, which stays its parent
        _tr.record_span("decode.step", rec.parent, rec.t0, t1,
                        attrs=rec.attrs)
        traced = [s for s in rows
                  if s.tctx is not None and s.tctx.sampled]
        if traced:
            sid = _tr.new_span_id()
            attrs = {"slots": len(rows), "bucket": nslots,
                     "live_pages": self._pool.used_pages}
            for sess in traced:
                _tr.record_span("decode.step", sess.tctx, rec.t0, t1,
                                span_id=sid,
                                parent_id=sess.tctx.span_id,
                                attrs=attrs)
        with self._cond:
            self._flight.remove(rec)
            for i, sess in enumerate(rows):
                if sess.done:            # ended before, or concurrently
                    continue
                if experts is not None:
                    sess.expert_choices.append(experts[:, i:i + 1])
                sess.pos += 1
                self._emit_locked(sess, int(out[i]))
            self._release_held_locked()

    def _learn(self, rec, waited, t_fetched):
        """The device time of a step of this bucket, from what the loop
        sees: a step queued while the one before it ran, whose fetch and
        whose predecessor's fetch both waited for the device, took the
        time between the two fetches; and no step took longer than from
        its dispatch to its fetch, which brings an estimate down that a
        step dispatched after its predecessor landed shows to be long."""
        est = self._step_s.get(rec.nslots)
        bound = t_fetched - rec.t0
        if est is None:
            est = bound
        elif rec.ahead and waited and self._landed_waited:
            est += _EWMA * ((t_fetched - self._t_landed) - est)
        self._step_s[rec.nslots] = min(est, bound)
        self._t_landed = t_fetched
        self._landed_waited = waited

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
                    toks = jnp.argmax(logits, -1).astype(jnp.int32)
                    out = toks
                    if moe:         # ... + layers * (1 + k * slots)
                        out = jnp.concatenate(
                            [out, stats["moe_active_experts"],
                             stats["moe_experts"].reshape(-1)])
                    # the tokens as the next step takes them when it goes
                    # ahead: a dummy slot's (position 0) at 0, as a step
                    # from host arrays has it
                    feed = jnp.where(pos > 0, toks, 0)
                    return (feed, out) + cache_pools(paged)

                return prog

            prog = _pg.get_or_build(
                _pg.ProgramKey("decode_step", self._graph_hash,
                               {"slots": int(nslots)}), build)
            self._step_progs[nslots] = prog
        return prog
