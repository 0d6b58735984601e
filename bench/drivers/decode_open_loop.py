"""Driver ``decode_open_loop``: a transformer behind ``serve.DecodeEngine``
under an OPEN loop — requests are sent on a schedule fixed by the traffic
file and ``--seed``, whether or not earlier ones have finished.

Path under test: ``TransformerConfig`` + a parameter tree +
``DecodeEngine(...).warmup()`` + ``submit()``; the engine's own scheduler
thread does the rest. The weights stand in for a loaded checkpoint: the
benchmark makes them on the device, in ONE jitted call from the seed, in
the type they are served in, over the tree that the configuration's plain
reference declares (``bench/reference/<config>.py:param_tree``) — this
file knows no model. The program's ``init_transformer_params`` is not the
path: it draws every number with numpy on the host, leaf by leaf (1.3 G
draws for the first configuration), which serves no request and which a
server that loads a checkpoint never runs; a test holds the reference's
tree equal to the one that function builds.

Schedule: the traffic file fixes the rate, the length distributions and a
``schedule_seed``; from those come ONE set of inter-arrival gaps (Poisson)
and ONE set of (prompt, output) lengths for the ramp and one for the
window, the same for every ``--seed``, which only starts the window's cycle
at another request and draws the prompt tokens. So the spread over seeds is
the system's, on one draw of the mix, and not the mix's. The ramp is
set-up; requests DUE in the window are measured and drained after it under
a timeout. Times are from the moment a request was due.

Configuration file keys: ``model`` (TransformerConfig sizes), ``dtype``,
``engine`` (slots, page_size, num_pages, max_context). Traffic file keys:
``rate_per_s``, ``prompt_tokens``/``output_tokens`` (lognormal ``median``,
``sigma``, ``min``, ``max``), ``schedule_seed``, ``ramp_seconds``,
``drain_timeout_s``, ``trace_start_s``, ``trace_seconds``,
``reference_requests``, ``reference_max_tokens``,
``reference_logit_tolerance``.
"""
import gc
import math
import threading
import time

import numpy as np

from bench import harness, stats, trace_reduce


def model_config(config):
    import jax.numpy as jnp
    from mxnet_tpu.parallel.transformer import TransformerConfig
    m = config["model"]
    return TransformerConfig(
        vocab_size=int(m["vocab_size"]), d_model=int(m["d_model"]),
        n_heads=int(m["n_heads"]), n_layers=int(m["n_layers"]),
        d_ff=int(m["d_ff"]), max_len=int(m["max_len"]),
        dtype=jnp.dtype(config["dtype"]).type, pos_type=m["pos_type"])


def _is_spec(x):
    return isinstance(x, tuple) and isinstance(x[1], str)


def make_params(reference, config, seed, device):
    """The weights of ``reference.param_tree``, on ``device``, in ONE
    jitted call from the seed, in the served dtype."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(config["dtype"])
    leaves, tree = jax.tree_util.tree_flatten(
        reference.param_tree(config["model"]), is_leaf=_is_spec)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind) in zip(keys, leaves):
            if kind == "normal":
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * reference.INIT_STD).astype(dtype))
            else:
                out.append(jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                    dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    with jax.default_device(device):
        key = jax.random.key(np.uint32(seed % (2 ** 32)))
        return jax.block_until_ready(jax.jit(build)(key))


def lognormal_lengths(rng, n, spec):
    vals = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def schedule(traffic, seconds, seed, vocab):
    """[(due seconds from the ramp's start, prompt tokens, output tokens)]
    for the ramp and then the window. Gaps (Poisson) and lengths are fixed
    by ``schedule_seed``, so no seed changes the work: the ramp is the same
    requests for every ``seed``, and the window holds the same requests as
    one cycle that ``seed`` starts at another request (each keeps its
    neighbours, so the bursts and long answers that make the tails stay
    what they are). ``seed`` also draws the prompt tokens."""
    fixed = np.random.RandomState(int(traffic["schedule_seed"]))
    mix = np.random.RandomState(seed % (2 ** 32))
    plan, start = [], 0.0
    for span, turn in ((float(traffic["ramp_seconds"]), False),
                       (float(seconds), True)):
        n = max(1, int(round(traffic["rate_per_s"] * span)))
        gaps = fixed.exponential(1.0, n)
        gaps *= span / gaps.sum() * (n - 0.5) / n
        prompts = lognormal_lengths(fixed, n, traffic["prompt_tokens"])
        outputs = lognormal_lengths(fixed, n, traffic["output_tokens"])
        if turn:
            k = int(mix.randint(0, n))
            gaps, prompts, outputs = (np.roll(a, -k)
                                      for a in (gaps, prompts, outputs))
        due = start + np.cumsum(gaps)
        plan += [(float(due[i]), int(prompts[i]), int(outputs[i]))
                 for i in range(n)]
        start += span
    return [(due, mix.randint(0, vocab, p).tolist(), o)
            for due, p, o in plan]


class Tracer(threading.Thread):
    """Traces ``seconds`` of the window from a thread of its own, so that
    ``start_trace``/``stop_trace`` do not hold up the load generator."""

    def __init__(self, ctx, at, seconds, clock, snapshot):
        super().__init__(name="bench-tracer", daemon=True)
        self.ctx, self.at, self.seconds = ctx, at, seconds
        self.clock, self.snapshot = clock, snapshot
        self.path = None
        self.host_window = None
        self.counters = None

    def run(self):
        import jax
        time.sleep(max(0.0, self.at - self.clock()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.ctx.fresh_trace_dir(),
                                 profiler_options=opts)
        c0, t0 = self.snapshot(), self.clock()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            time.sleep(self.seconds)
        c1, t1 = self.snapshot(), self.clock()
        jax.profiler.stop_trace()
        self.host_window = (t0, t1)
        self.counters = dict((k, c1[k] - c0[k]) for k in c0)
        self.path = trace_reduce.find_xplane(self.ctx.trace_dir)


def _reference_check(ctx, cfg, params, finished):
    """For a seeded sample of finished requests, the plain reference run
    ONCE over prompt + generated tokens must give every generated token a
    logit within the tolerance of that position's maximum (the engine
    returns tokens, not logits, and with random weights the argmax flips
    on rounding)."""
    tr = ctx.traffic
    want = int(tr["reference_requests"])
    cap = int(tr["reference_max_tokens"])
    tol = float(tr["reference_logit_tolerance"])
    short = [r for r in finished
             if len(r["prompt"]) + len(r["tokens"]) <= cap]
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    picks = [short[i] for i in sorted(rng.permutation(len(short))[:want])]
    if not picks:
        return False, "no finished request of <= %d tokens to compare" % cap
    ref = ctx.cell.reference()
    worst, checked = 0.0, 0
    for r in picks:
        seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
        logits = np.asarray(ref.forward(params, seq, cfg.n_heads,
                                        pad_to=cap))
        first = len(r["prompt"]) - 1        # the position that predicts
        rows = logits[first:first + len(r["tokens"])]     # token 0
        gap = rows.max(axis=-1) - rows[np.arange(len(r["tokens"])),
                                       np.asarray(r["tokens"])]
        worst = max(worst, float(gap.max()))
        checked += len(r["tokens"])
    return worst <= tol, ("%d generated tokens of %d requests: largest "
                          "(max logit - chosen logit) = %.4f (tolerance %g)"
                          % (checked, len(picks), worst, tol))


def run(ctx):
    try:
        import jax
        from mxnet_tpu import programs, telemetry as tm
        from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine
    except ImportError as e:
        raise harness.Refused("cannot import the program (%s)" % e)
    tr, eng_cfg = ctx.traffic, ctx.config["engine"]
    cfg = model_config(ctx.config)
    clock = tm.monotonic                    # the sessions' own clock
    harness.say("compile cache: %s" % programs.cache_dir())

    t = time.perf_counter()
    params = make_params(ctx.cell.reference(), ctx.config, ctx.seed,
                         ctx.devices[0])
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    harness.say("%.3f G parameters (%s) on %s in %.1fs"
                % (n_params / 1e9, ctx.config["dtype"], ctx.devices[0],
                   time.perf_counter() - t))

    t = time.perf_counter()
    real0 = tm.counter("programs/compile_total").value
    disk0 = tm.counter("programs/disk_hits_total").value
    with jax.default_device(ctx.devices[0]):
        engine = DecodeEngine(params, cfg, DecodeConfig(
            slots=eng_cfg["slots"], page_size=eng_cfg["page_size"],
            num_pages=eng_cfg["num_pages"],
            max_context=eng_cfg["max_context"],
            queue_depth=eng_cfg["queue_depth"],
            max_new_tokens=tr["output_tokens"]["max"],
            default_timeout_ms=int(eng_cfg["deadline_s"] * 1e3)))
        engine.warmup(timeout=float(eng_cfg["warmup_timeout_s"]))
    harness.say("engine warm in %.1fs: %d programs, %d real compiles, %d "
                "disk loads; pool %d pages of %d tokens"
                % (time.perf_counter() - t, engine.program_count(),
                   tm.counter("programs/compile_total").value - real0,
                   tm.counter("programs/disk_hits_total").value - disk0,
                   eng_cfg["num_pages"], eng_cfg["page_size"]))

    # .labels() of an unlabeled family is its one histogram
    h_step = tm.histogram("decode/step_seconds").labels()
    h_prefill = tm.histogram("decode/prefill_seconds").labels()

    def snapshot():
        return {"tokens": tm.counter("decode/tokens_total").value,
                "requests": tm.counter("decode/requests_total").value,
                "steps": h_step.count, "step_seconds": h_step.sum,
                "prefills": h_prefill.count,
                "prefill_seconds": h_prefill.sum,
                "real_compiles": tm.counter("programs/compile_total").value,
                "disk_loads": tm.counter("programs/disk_hits_total").value}

    plan = schedule(tr, ctx.seconds, ctx.seed, cfg.vocab_size)
    ramp = float(tr["ramp_seconds"])
    t_zero = clock() + 0.05
    t_begin, t_end = t_zero + ramp, t_zero + ramp + ctx.seconds
    tracer = None
    if ctx.trace:
        tracer = Tracer(ctx, t_begin + float(tr["trace_start_s"]),
                        float(tr["trace_seconds"]), clock, snapshot)
        tracer.start()

    # -- the open loop: this thread only sleeps and submits -----------------
    sent = []                               # (Request, session | None)
    c_begin = None
    for i, (due_rel, prompt, want) in enumerate(plan):
        due = t_zero + due_rel
        if c_begin is None and due >= t_begin:
            c_begin, t_c_begin = snapshot(), clock()
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        req = stats.Request(due=due, sent=clock(), prompt_len=len(prompt),
                            want_tokens=want)
        try:
            sess = engine.submit(prompt, max_new_tokens=want)
        except Exception as e:              # refused: counts as failed
            req.error, sess = "%s: %s" % (type(e).__name__, e), None
        sent.append((req, sess))
    if c_begin is None:
        c_begin, t_c_begin = snapshot(), clock()
    wait = t_end - clock()
    if wait > 0:
        time.sleep(wait)
    c_end, t_c_end = snapshot(), clock()
    memory_peak = harness.memory_peak_bytes(ctx.devices)

    # -- drain the measured requests under a timeout --------------------------
    give_up = clock() + float(tr["drain_timeout_s"])
    for _req, sess in sent:
        while sess is not None and not sess.done and clock() < give_up:
            time.sleep(0.005)
    t_drained = clock()
    if tracer is not None:
        tracer.join()
    engine.close(drain=False, timeout=30.0)

    reqs, finished, every = [], [], []    # due in the window; ok; all
    for (req, sess), (_due, prompt, _want) in zip(sent, plan):
        if sess is not None:
            req.first, req.tokens = sess.t_first, len(sess.out_tokens)
            req.enq, req.admit = sess.t_enq, sess.t_admit
            if sess.done:
                req.done = sess.t_done
                if sess.error is not None:
                    req.error = "%s: %s" % (type(sess.error).__name__,
                                            sess.error)
        if req.failed:
            req.gave_up = t_drained
        else:
            finished.append({"prompt": prompt,
                             "tokens": list(sess.out_tokens)})
        every.append(req)
        if t_begin <= req.due < t_end:
            reqs.append(req)
    failed = [r for r in reqs if r.failed]
    tpot = [v for v in (stats.tpot_ms(r) for r in reqs) if v is not None]
    ttft = [stats.ttft_ms(r) for r in reqs]
    window_counts = dict((k, c_end[k] - c_begin[k]) for k in c_begin)
    harness.say("window: %d requests due in %.1fs (%d failed), %d output "
                "tokens asked for; generator lateness p95 %.2f ms; drained "
                "%.1fs after the window"
                % (len(reqs), ctx.seconds, len(failed),
                   sum(r.want_tokens for r in reqs),
                   stats.percentile([stats.lateness_ms(r) for r in reqs],
                                    95) or 0.0, t_drained - t_end))
    # below the knee the rate of tokens is the offered load and no metric;
    # here for the record: what the engine made INSIDE the window
    harness.say("engine: %d tokens in %d steps and %d prefills inside the "
                "window = %.1f tokens/s; ttft ms mean %.2f p50 %.2f p95 %.2f"
                % (window_counts["tokens"], window_counts["steps"],
                   window_counts["prefills"],
                   window_counts["tokens"] / (t_c_end - t_c_begin),
                   stats.mean(ttft), stats.percentile(ttft, 50),
                   stats.percentile(ttft, 95)))
    for r in failed[:3]:
        harness.say("failed: %s (tokens %d of %d)"
                    % (r.error, r.tokens, r.want_tokens))

    # -- correct ---------------------------------------------------------------
    compiles = window_counts["real_compiles"] + window_counts["disk_loads"]
    checks = [
        ("zero_compiles_in_window", compiles == 0,
         "%d real compile(s), %d disk load(s) in the window"
         % (window_counts["real_compiles"], window_counts["disk_loads"])),
        ("no_request_failed", not failed,
         "%d of %d requests due in the window failed, were refused or "
         "timed out" % (len(failed), len(reqs))),
        # greedy, no stop token: a request gets exactly the tokens it
        # asked for, or it is counted failed; none gets more
        ("none_lost_or_duplicated",
         all(r.tokens == r.want_tokens or r.failed for r in every)
         and all(r.tokens <= r.want_tokens for r in every),
         "%d sent, %d finished whole, %d failed"
         % (len(every), len(finished), len(every) - len(finished))),
    ]
    # the engine and its pool go before the reference comes
    del engine, sent
    gc.collect()
    checks.append(("tokens_agree_with_reference",)
                  + _reference_check(ctx, cfg, params, finished))

    samples = {
        "requests": reqs, "slots": int(eng_cfg["slots"]),
        "window_counts": window_counts, "window_s": ctx.seconds,
        "all_requests": every,          # ramp and window
        "kv_itemsize": np.dtype(cfg.dtype).itemsize,
        # the traced part of the window on the sessions' clock, and the
        # counters' deltas over it
        "trace_host_window": tracer.host_window if tracer else None,
        "trace_counts": tracer.counters if tracer else None,
    }
    return {
        "end_to_end": {
            "serve_ttft_mean_ms": stats.mean(ttft),
            "serve_tpot_p95_ms": stats.percentile(tpot, 95),
            "setup_s": t_begin - ctx.t0},
        "attempted": len(reqs), "failed": len(failed), "checks": checks,
        "memory_peak_bytes": memory_peak,
        "trace_path": tracer.path if tracer is not None else None,
        "samples": samples,
        "counters": {"compiles_in_window": compiles},
    }


def aot_check(cell, hbm, aot):
    """``bench/aot_check.py``: the largest prefill bucket and the largest
    slot bucket at the configuration's pool, compiled for a described
    v5e. Weights and pool are arguments of both programs, so the live
    bytes it prints are what the chip must hold while one runs."""
    import jax
    from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine
    one = jax.sharding.SingleDeviceSharding(aot.describe().devices[0])
    cfg = model_config(cell.config)
    eng = cell.config["engine"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], cfg.dtype, sharding=one),
        cell.reference().param_tree(cell.config["model"]), is_leaf=_is_spec)
    # a two-page pool to build the engine; the programs take the pool as
    # an argument and are lowered at the configuration's size
    engine = DecodeEngine(params, cfg, DecodeConfig(
        slots=eng["slots"], page_size=eng["page_size"], num_pages=2,
        max_context=eng["max_context"], queue_depth=eng["queue_depth"]))
    dcfg = engine.config
    hd = cfg.d_model // cfg.n_heads
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, eng["num_pages"], eng["page_size"], cfg.n_heads, hd),
        cfg.dtype, sharding=one)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=one)
    weights = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                  for p in jax.tree_util.tree_leaves(params))
    print("weights %.3f GB; pool 2 x %.3f GB = %d pages = %d tokens"
          % (weights / aot.GB, int(np.prod(pool.shape)) * 2 / aot.GB,
             eng["num_pages"], eng["num_pages"] * eng["page_size"]))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"     # on_tpu(): the Mosaic kernels
    try:
        bucket = dcfg.prefill_buckets[-1]
        prefill = engine._prefill_prog(bucket).lower(
            params, pool, pool, i32(bucket // dcfg.page_size),
            i32(1, bucket), i32(1)).compile()
        slots = dcfg.slot_buckets[-1]
        step = engine._step_prog(slots).lower(
            params, pool, pool, i32(slots, dcfg.pages_per_seq), i32(slots),
            i32(slots)).compile()
    finally:
        jax.default_backend = real_backend
    for name, prog in (("prefill", prefill), ("step", step)):
        text = prog.as_text()
        print("%s: %d Mosaic custom calls, %d copies of pool-sized arrays"
              % (name, text.count("tpu_custom_call"),
                 sum(1 for ln in text.splitlines()
                     if " copy(" in ln and "%d,%d,%d,%d" % (
                         eng["num_pages"], eng["page_size"], cfg.n_heads, hd)
                     in ln)))
    return max(aot.report("decode_prefill[%d]" % bucket, prefill, hbm),
               aot.report("decode_step[%d]" % slots, step, hbm))
