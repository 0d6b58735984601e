"""Driver ``decode_open_loop_v2``: ``decode_open_loop``'s open loop — the
same schedule rule, ``Tracer``, weights from ``reference.param_tree`` in
one jitted call, ``stats``, checks and result line — for a configuration
that the first driver's seven named sizes cannot describe. It differs in:

* ``TransformerConfig(**config["model"], dtype=...)``: the configuration
  file's ``model`` IS the program's config, so a later architecture edits
  no driver (a program whose ``TransformerConfig`` lacks a field refuses
  the run at once);
* ``prompt_tokens`` may be a LIST of weighted classes (``weight`` and the
  lognormal's ``median``, ``sigma``, ``min``, ``max``); which class a
  request is, like its lengths and gaps, is drawn from ``schedule_seed``,
  so ``--seed`` still only rotates the window's start and draws the ids;
* the engine may hold a second pool (``engine.window_pages``: the
  sliding-window layers' rings), and ``aot_check`` takes the pools' shapes
  from the program (``init_kv_pages``), whatever kinds there are;
* the weights are drawn a layer at a time INSIDE the one jitted call
  (``lax.map`` over the stacks' layer axis): a 1.5 G-element stack of
  experts drawn whole holds 6 GB of float32 before its cast;
* ``correct`` compares ``reference_requests`` short finished requests and
  ``reference_long_requests`` long ones (prompt >= ``reference_long_
  prompt_min``, whole sequence <= ``reference_max_tokens``; a run with no
  such request to compare FAILS the check) — the logits as the first
  driver does, their MEAN gap beside the largest, and the routing: the
  share of (position, layer) pairs whose chosen experts are the
  reference's, where the program's are those its TIMED prefill and its
  TIMED decode steps chose for that request (``DecodeSession.
  expert_choices``), each part in the request that agrees least against
  a floor of its own. A traced run
  also takes the same readings against each of ``reference_controls`` —
  the reference with its weights rounded to the precision below the
  served one, or with a position rule broken — and prints which limit
  refuses each (they decide nothing; the limits were set from them);
* a traced run splits the device time of the kernels named in
  ``KERNEL_WORK`` between decode steps and prefills (the program's logged
  spans laid on the trace's clock by the ``bench.window`` annotation).

Folding the two drivers into one is a ``benchmark`` issue's.
"""
import gc
import os
import re
import time

import numpy as np

from bench import harness, span_log, stats, trace_reduce

_v1 = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode_open_loop.py"))
Tracer, lognormal_lengths, _is_spec = (_v1.Tracer, _v1.lognormal_lengths,
                                       _v1._is_spec)

# bench/work/<name>.py of the kernels whose time a traced run splits
KERNEL_WORK = ("moe_grouped_ffn", "paged_decode_attention")


def model_config(config):
    import jax.numpy as jnp
    from mxnet_tpu.parallel.transformer import TransformerConfig
    model = dict((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in config["model"].items())
    try:
        return TransformerConfig(
            dtype=jnp.dtype(config["dtype"]).type, **model)
    except TypeError as e:
        raise harness.Refused("the program's TransformerConfig cannot "
                              "describe this configuration: %s" % e)


def make_params(reference, config, seed, device):
    """The weights of ``reference.param_tree``, on ``device``, in ONE
    jitted call from the seed, in the served dtype; a stack ``(1, L,
    ...)`` is drawn a layer at a time."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(config["dtype"])
    leaves, tree = jax.tree_util.tree_flatten(
        reference.param_tree(config["model"]), is_leaf=_is_spec)

    def draw(key, shape, kind):
        return (jax.random.normal(key, shape, jnp.float32)
                * reference.init_std(kind, config["model"])).astype(dtype)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind) in zip(keys, leaves):
            if kind == "ones":
                out.append(jnp.ones(shape, dtype))
            elif len(shape) > 2 and shape[0] == 1:
                out.append(jax.lax.map(
                    lambda lk: draw(lk, shape[2:], kind),
                    jax.random.split(k, shape[1]))[None])
            else:
                out.append(draw(k, shape, kind))
        return jax.tree_util.tree_unflatten(tree, out)

    with jax.default_device(device):
        key = jax.random.key(np.uint32(seed % (2 ** 32)))
        return jax.block_until_ready(jax.jit(build)(key))


def prompt_lengths(rng, n, spec):
    """``spec``: one lognormal, or a list of weighted classes."""
    if isinstance(spec, dict):
        return lognormal_lengths(rng, n, spec)
    weights = np.array([c["weight"] for c in spec], float)
    which = rng.choice(len(spec), size=n, p=weights / weights.sum())
    drawn = [lognormal_lengths(rng, n, c) for c in spec]
    return np.choose(which, drawn)


def schedule(traffic, seconds, seed, vocab):
    """``decode_open_loop.schedule`` with classes of prompts: gaps,
    classes and lengths are fixed by ``schedule_seed``; ``seed`` starts
    the window's cycle at another request and draws the prompt tokens."""
    fixed = np.random.RandomState(int(traffic["schedule_seed"]))
    mix = np.random.RandomState(seed % (2 ** 32))
    plan, start = [], 0.0
    for span, turn in ((float(traffic["ramp_seconds"]), False),
                       (float(seconds), True)):
        n = max(1, int(round(traffic["rate_per_s"] * span)))
        gaps = fixed.exponential(1.0, n)
        gaps *= span / gaps.sum() * (n - 0.5) / n
        prompts = prompt_lengths(fixed, n, traffic["prompt_tokens"])
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


def _readings(ref, params, model, picks, cap, out_max, weights_as=None):
    """The reference over each picked request's whole sequence against
    what the timed engine did with it: the gaps of its generated tokens
    (the reference's largest logit less the chosen token's, a position)
    and the share of (position, layer) pairs that chose the reference's
    experts — the prompt's positions by the request's prefill, the
    generated ones by its decode steps — over all the requests and in
    the WORST of them (a fault of long contexts shows in the long
    request alone, a quarter of the pairs)."""
    gaps, same = [], {"prefill": [], "decode": []}
    for r in picks:
        n_prompt, tokens = len(r["prompt"]), np.asarray(r["tokens"])
        seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
        logits, ref_experts = ref.forward(   # n_prompt - 1 predicts token 0
            params, seq, model, pad_to=cap, logits_from=n_prompt - 1,
            logits_rows=out_max, weights_as=weights_as)
        rows = np.asarray(logits)[:len(tokens)]
        gaps.append(rows.max(axis=-1) - rows[np.arange(len(tokens)), tokens])
        # (L, positions fed, k): the last token is never fed
        served = np.sort(np.concatenate(r["experts"], axis=1), -1)
        agree = np.all(served == np.sort(np.asarray(
            ref_experts)[:, :served.shape[1]], -1), axis=-1)
        same["prefill"].append(agree[:, :n_prompt].ravel())
        same["decode"].append(agree[:, n_prompt:].ravel())
    gaps = np.concatenate(gaps)
    out = {"tokens": gaps.size, "gap_max": float(gaps.max()),
           "gap_mean": float(gaps.mean()),
           "argmax_share": float(np.mean(gaps == 0.0)),
           "prompts": [len(r["prompt"]) for r in picks]}
    for part, flags in same.items():
        shares = [float(f.mean()) for f in flags if f.size]
        out[part + "_pairs"] = sum(f.size for f in flags)
        out[part + "_agreement"] = float(np.concatenate(flags).mean())
        out[part + "_agreement_by_request"] = [round(v, 4) for v in shares]
        out[part + "_agreement_worst"] = min(shares)
    return out


def _judge(reading, tr):
    """The comparison's checks over one reading, each against its limit
    in the traffic file."""
    tol = float(tr["reference_logit_tolerance"])
    tol_mean = float(tr["reference_mean_logit_gap_max"])
    checks = [
        ("tokens_agree_with_reference",
         reading["gap_max"] <= tol and reading["gap_mean"] <= tol_mean,
         "%d generated tokens: (max logit - chosen logit) largest %.4f "
         "(tolerance %g), mean %.5f (at most %g); the reference's own "
         "choice for %.4f of them"
         % (reading["tokens"], reading["gap_max"], tol,
            reading["gap_mean"], tol_mean, reading["argmax_share"]))]
    for part, what in (("prefill", "its prefill"),
                       ("decode", "its decode steps")):
        floor = float(tr["reference_%s_routing_agreement_min" % part])
        worst = reading[part + "_agreement_worst"]
        checks.append((
            "%s_routing_agrees_with_reference" % part, worst >= floor,
            "the request whose (position, layer) pairs routed by %s "
            "agree least with the reference's experts: %.4f (at least %g); "
            "%.4f of all %d pairs"
            % (what, worst, floor, reading[part + "_agreement"],
               reading[part + "_pairs"])))
    return checks


def _reference_check(ctx, params, finished):
    """Short finished requests and long ones against the reference."""
    tr = ctx.traffic
    cap = int(tr["reference_max_tokens"])
    long_min = int(tr["reference_long_prompt_min"])
    want_long = int(tr["reference_long_requests"])
    fits = [r for r in finished
            if len(r["prompt"]) + len(r["tokens"]) <= cap]
    rng = np.random.RandomState(ctx.seed % (2 ** 32))

    def pick(pool, want):
        return [pool[i] for i in sorted(rng.permutation(len(pool))[:want])]

    short = pick([r for r in fits if len(r["prompt"]) < long_min],
                 int(tr["reference_requests"]))
    long_ = pick([r for r in fits if len(r["prompt"]) >= long_min],
                 want_long)
    names = ["tokens_agree_with_reference"] + [
        p + "_routing_agrees_with_reference" for p in ("prefill", "decode")]
    if not short or len(long_) < want_long:
        why = ("%d short and %d long finished request(s) of <= %d tokens "
               "to compare; %d long needed"
               % (len(short), len(long_), cap, want_long))
        return [(name, False, why) for name in names]
    ref, model = ctx.cell.reference(), ctx.config["model"]
    out_max = int(tr["output_tokens"]["max"])
    t = time.perf_counter()
    sound = _readings(ref, params, model, short + long_, cap, out_max)
    harness.say("reference over %d short and %d long request(s) in %.1fs: "
                "%s" % (len(short), len(long_), time.perf_counter() - t,
                        sound))
    for control in (tr.get("reference_controls") or []) if ctx.trace else []:
        reading = _readings(
            ref, params, dict(model, **control.get("model", {})),
            short + long_, cap, out_max, control.get("weights_as"))
        refused = [name for name, ok, _d in _judge(reading, tr) if not ok]
        harness.say("control %s: %s; %s"
                    % (control["name"],
                       "refused by " + ", ".join(refused) if refused
                       else "PASSES every limit", reading))
    return _judge(sound, tr)


def _kernel_split(ctx, tracer):
    """{work name: {"step_s", "prefill_s", "other_s", "calls"}}: device-0
    time of each ``KERNEL_WORK`` kernel inside the traced part, by the
    logged span (``decode.step`` / ``decode.prefill``) its event began
    in. The spans are on the sessions' clock; the trace's clock is laid
    on it by the ``bench.window`` annotation, whose opening the tracer
    stamped on both."""
    events = trace_reduce.load(tracer.path)
    window = [e for e in events if e.plane == trace_reduce.HOST_PLANE
              and e.name == trace_reduce.WINDOW_ANNOTATION]
    if not window or not tracer.host_window:
        return None
    origin = tracer.host_window[0] - min(e.start for e in window)
    spans = dict((name, sorted((r["t0"] - origin, r["t1"] - origin)
                               for r in span_log.records()
                               if r["name"] == name))
                 for name in ("decode.step", "decode.prefill"))
    lo = min(e.start for e in window)
    hi = max(e.start + e.dur for e in window)
    device = [e for e in events if e.plane == "/device:TPU:0"
              and e.start >= lo and e.start + e.dur <= hi]
    out = {}
    for work in KERNEL_WORK:
        rx = re.compile(ctx.cell.work(work).TRACE_NAME)
        split = {"step_s": 0.0, "prefill_s": 0.0, "other_s": 0.0,
                 "calls": 0}
        for e in device:
            if not rx.search(e.name):
                continue
            where = next((key for key, name in (
                ("step_s", "decode.step"), ("prefill_s", "decode.prefill"))
                if any(a <= e.start <= b for a, b in spans[name])),
                "other_s")
            split[where] += e.dur
            split["calls"] += 1
        out[work] = split
    return out


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
    try:
        dcfg = DecodeConfig(
            slots=eng_cfg["slots"], page_size=eng_cfg["page_size"],
            num_pages=eng_cfg["num_pages"],
            window_pages=eng_cfg.get("window_pages"),
            max_context=eng_cfg["max_context"],
            queue_depth=eng_cfg["queue_depth"],
            max_new_tokens=tr["output_tokens"]["max"],
            default_timeout_ms=int(eng_cfg["deadline_s"] * 1e3))
    except TypeError as e:
        raise harness.Refused("the program's DecodeConfig cannot take "
                              "this engine: %s" % e)
    with jax.default_device(ctx.devices[0]):
        engine = DecodeEngine(params, cfg, dcfg)
        engine.warmup(timeout=float(eng_cfg["warmup_timeout_s"]))
    pools = {"global": int(eng_cfg["num_pages"]) - 1}
    if eng_cfg.get("window_pages"):
        pools["window"] = int(eng_cfg["window_pages"]) - 1
    harness.say("engine warm in %.1fs: %d programs, %d real compiles, %d "
                "disk loads; pools (pages of %d tokens) %s"
                % (time.perf_counter() - t, engine.program_count(),
                   tm.counter("programs/compile_total").value - real0,
                   tm.counter("programs/disk_hits_total").value - disk0,
                   eng_cfg["page_size"], pools))

    # .labels() of an unlabeled family is its one histogram
    h_step = tm.histogram("decode/step_seconds").labels()
    h_prefill = tm.histogram("decode/prefill_seconds").labels()
    free = tm.gauge("decode/pages_free")

    def snapshot():
        return {"tokens": tm.counter("decode/tokens_total").value,
                "requests": tm.counter("decode/requests_total").value,
                "steps": h_step.count, "step_seconds": h_step.sum,
                "prefills": h_prefill.count,
                "prefill_seconds": h_prefill.sum,
                "moe_rows": tm.counter(
                    "decode/moe_assignments_total").value,
                "moe_active": tm.counter(
                    "decode/moe_expert_activations_total").value,
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
    pages_used = dict((kind, []) for kind in pools)
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
        if due >= t_begin:
            # the pools as each arrival of the window found them
            for kind, capacity in pools.items():
                pages_used[kind].append(
                    1.0 - free.labels(kind).value / float(capacity))
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
    kernel_split = None
    if tracer is not None:
        tracer.join()
        if tracer.path:
            t = time.perf_counter()
            kernel_split = _kernel_split(ctx, tracer)
            harness.say("kernel time by span in %.1fs: %s"
                        % (time.perf_counter() - t, kernel_split))
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
                             "tokens": list(sess.out_tokens),
                             "experts": sess.expert_choices})
        every.append(req)
        if t_begin <= req.due < t_end:
            reqs.append(req)
    failed = [r for r in reqs if r.failed]
    tpot = [v for v in (stats.tpot_ms(r) for r in reqs) if v is not None]
    ttft = [stats.ttft_ms(r) for r in reqs]
    window_counts = dict((k, c_end[k] - c_begin[k]) for k in c_begin)
    harness.say("window: %d requests due in %.1fs (%d failed; %d with a "
                "prompt past the window), %d output tokens asked for; "
                "generator lateness p95 %.2f ms; drained %.1fs after the "
                "window"
                % (len(reqs), ctx.seconds, len(failed),
                   sum(1 for r in reqs
                       if r.prompt_len > (cfg.sliding_window or 1 << 30)),
                   sum(r.want_tokens for r in reqs),
                   stats.percentile([stats.lateness_ms(r) for r in reqs],
                                    95) or 0.0, t_drained - t_end))
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
    # the engine and its pools go before the reference comes
    del engine, sent
    gc.collect()
    checks += _reference_check(ctx, params, finished)

    samples = {
        "requests": reqs, "slots": int(eng_cfg["slots"]),
        "window_counts": window_counts, "window_s": ctx.seconds,
        "window_host": (t_begin, t_end),
        "all_requests": every,          # ramp and window
        "kv_itemsize": np.dtype(cfg.dtype).itemsize,
        "pages_used": pages_used,
        "kernel_split": kernel_split,
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
    slot bucket at the configuration's pools, and the one call that makes
    the weights, compiled for a described v5e. Weights and pools are
    arguments of both programs, so the live bytes it prints are what the
    chip must hold while one runs."""
    import jax
    from mxnet_tpu.parallel.transformer import init_kv_pages
    from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine
    one = jax.sharding.SingleDeviceSharding(aot.describe().devices[0])
    cfg = model_config(cell.config)
    eng = cell.config["engine"]
    tree = cell.reference().param_tree(cell.config["model"])
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], cfg.dtype, sharding=one),
        tree, is_leaf=_is_spec)
    # two-page pools to build the engine; the programs take the pools as
    # arguments and are lowered at the configuration's size
    engine = DecodeEngine(params, cfg, DecodeConfig(
        slots=eng["slots"], page_size=eng["page_size"], num_pages=2,
        window_pages=2 if eng.get("window_pages") else None,
        max_context=eng["max_context"], queue_depth=eng["queue_depth"]))
    dcfg = engine.config
    pages = ((eng["num_pages"], eng["window_pages"])
             if eng.get("window_pages") else eng["num_pages"])
    k_pool, v_pool = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: init_kv_pages(cfg, pages, eng["page_size"])))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=one)
    nbytes = lambda t: sum(int(np.prod(p.shape)) * p.dtype.itemsize
                           for p in jax.tree_util.tree_leaves(t))
    print("weights %.3f GB; pools (k and v) %s GB"
          % (nbytes(params) / aot.GB,
             " + ".join("%.3f" % (2 * nbytes(p) / aot.GB)
                        for p in jax.tree_util.tree_leaves(k_pool))))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"     # on_tpu(): the Mosaic kernels
    try:
        bucket = dcfg.prefill_buckets[-1]
        n_pb = bucket // dcfg.page_size
        prefill = engine._prefill_prog(bucket).lower(
            params, k_pool, v_pool,
            engine._tables(i32(n_pb), i32(engine._ring_pages or 0)),
            i32(1, bucket), i32(1)).compile()
        slots = dcfg.slot_buckets[-1]
        step = engine._step_prog(slots).lower(
            params, k_pool, v_pool,
            engine._tables(i32(slots, dcfg.pages_per_seq),
                           i32(slots, engine._ring_pages or 0)),
            i32(slots), i32(slots)).compile()
    finally:
        jax.default_backend = real_backend
    for name, prog in (("prefill", prefill), ("step", step)):
        print("%s: %d Mosaic custom calls"
              % (name, prog.as_text().count("tpu_custom_call")))
    return max(aot.report("decode_prefill[%d]" % bucket, prefill, hbm),
               aot.report("decode_step[%d]" % slots, step, hbm))
