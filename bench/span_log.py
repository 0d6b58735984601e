"""The program's own span log (``mxnet_tpu.tracing.span_log()``) laid
against a run: against the device's idle gaps of the traced window in a
training cell, against the measured window in a serving cell.

A record of the log is a span's dict: ``name``, ``span_id``/``parent_id``,
``attrs``, ``t0``/``t1`` in ``perf_counter`` seconds (the clock of the
serving driver and of the sessions' stamps), stamped beside the start and
end of the span's own event in the profiler's trace. The ``.xplane.pb``
counts from its session's start and names that instant only inside the file
(``profile_start_time``), which ``trace_reduce.Summary`` does not keep: so
the trace's ORIGIN on the log's clock is found here from instants that both
have, and the guards below hold the two to each other. A reader that finds
no log (a program without one), no trace, or clocks that fail a guard gets
``None``, with the reason printed once; nothing here raises on what a run
may lack.

Training (``fit_idle_ms``). The benchmark opens each ``bench.fit_step``
inside its ``batch_end_callback`` as the callback's last act, so every step
boundary lies inside one logged ``train.callbacks`` span, a few tens of
microseconds before its end; which span is known by counting, since the log
holds one per callback the driver counted. The origin is the least of
(span end - boundary); guard: all boundaries but one agree on it to 0.5 ms
(a pause may hold one span's end back), and each lies inside its span to
0.5 ms. A log whose clock steps or drifts inside the window, or that lost
or gained a span, fails; a log shifted as a whole cannot be told from
another origin (the file's own ``profile_start_time`` could: a
``benchmark`` issue's).

WHAT THE SPLIT RESOLVES. The gaps are the DEVICE plane's, the spans the
host's, and the two planes are not held to each other here: on the chip a
probe's device op was stamped 1.1 ms before the host annotation that
dispatched it (PERF.md, PR 24). An ``idle_*`` reading of a millisecond or
two is therefore under the floor: it says "about nothing", and no change
is to be judged on it. The five always add up to the device's idle time.

Serving (``decode_window``). The metrics are taken on the sessions' clock
over the measured window, from the log alone. Guards: the ``decode.step``
spans logged inside the driver's ``trace_host_window`` number what the step
histogram counted there, give or take one at each edge (the log is whole
and on the sessions' clock), and that window is as long on the log's clock
as the trace's ``bench.window`` is on the trace's, to 0.5 ms (the two
clocks run at one rate).
"""
from bench import harness, trace_reduce

TOLERANCE_S = 0.5e-3
STEP_ANNOTATION = "bench.fit_step"
CALLBACKS = "train.callbacks"
FIT_PHASES = ("executor.stage_input", "executor.train_step",
              "train.update_metric", CALLBACKS)
OTHER = "other"


def records():
    """The program's span log, oldest first; ``[]`` where the program
    keeps none (a commit from before the log)."""
    try:
        from mxnet_tpu import tracing
    except ImportError:
        return []
    log = getattr(tracing, "span_log", None)
    if log is None:
        return []
    return log()


def intersect(a, b):
    """The part of merged intervals ``a`` that merged ``b`` covers."""
    return trace_reduce.subtract(a, trace_reduce.subtract(a, b))


def named(log, name):
    return sorted((r for r in log if r["name"] == name),
                  key=lambda r: r["t0"])


def _memo(run, key, compute):
    """One computation (and one printed reason) per run, however many
    metric files ask."""
    cache = run.__dict__.setdefault("_span_log", {})
    if key not in cache:
        value, why = compute()
        if value is None:
            harness.say("span log (%s): nothing to read: %s" % (key, why))
        cache[key] = value
    return cache[key]


# -- training -----------------------------------------------------------------

def whole_steps(trace):
    lo, hi = trace.window
    return sorted((s, e) for s, e in trace.annotations.get(STEP_ANNOTATION, ())
                  if s >= lo - 1e-9 and e <= hi + 1e-9)


def fit_origin(steps, phases, callbacks):
    """``(origin, None)`` or ``(None, why)``: the instant the trace counts
    from, in seconds on the log's clock. ``steps``: the whole
    ``bench.fit_step`` intervals (trace seconds); ``phases``: the driver's
    label of every callback it counted, warm-up included; ``callbacks``:
    the logged ``train.callbacks`` spans, oldest first."""
    if len(callbacks) != len(phases):
        return None, ("%d train.callbacks spans logged, the driver counted "
                      "%d callbacks" % (len(callbacks), len(phases)))
    # the callback that started the trace, those that re-opened the step
    # annotation, the one that stopped the trace: one more than the steps
    inside = [c for c, p in zip(callbacks, phases) if p in ("edge", "traced")]
    if len(inside) != len(steps) + 1:
        return None, ("%d whole %s in the trace, %d callbacks inside it"
                      % (len(steps), STEP_ANNOTATION, len(inside)))
    lead = [c["t1"] - s for c, (s, _e) in zip(inside, steps)]
    origin = min(lead)
    # a pause between the annotation and the span's end makes one lead
    # long; a clock that steps or drifts makes all that follow long
    off = sorted(d - origin for d in lead)
    late = off[-2] if len(off) > 1 else off[-1]
    if late > TOLERANCE_S:
        return None, ("the step boundaries do not agree on one origin: all "
                      "but one within %.3f ms" % (late * 1e3))
    ends = [s for s, _e in steps] + [steps[-1][1]]
    for c, at in zip(inside, ends):
        at += origin
        if not c["t0"] - TOLERANCE_S <= at <= c["t1"] + TOLERANCE_S:
            return None, ("a step boundary lies %.3f ms outside its "
                          "train.callbacks span"
                          % (max(c["t0"] - at, at - c["t1"]) * 1e3))
    return origin, None


def _fit_idle(run):
    trace = run.trace
    if trace is None or trace.window_s <= 0 or not trace.gaps:
        return None, "no device trace"
    steps = whole_steps(trace)
    if not steps:
        return None, "no whole %s in the trace" % STEP_ANNOTATION
    log = records()
    if not log:
        return None, "the program keeps no span log"
    counted = run.samples.get("warmup", []) + run.samples.get("steps", [])
    origin, why = fit_origin(steps, [s["phase"] for s in counted],
                             named(log, CALLBACKS))
    if origin is None:
        return None, why
    lo, hi = steps[0][0], steps[-1][1]
    idle = trace_reduce.clip(trace_reduce.union(
        (start, start + sec) for sec, start, _name in trace.gaps), lo, hi)
    out = {}
    for phase in FIT_PHASES:
        spans = trace_reduce.union((r["t0"] - origin, r["t1"] - origin)
                                   for r in log if r["name"] == phase)
        out[phase] = trace_reduce.total(intersect(idle, spans))
    out[OTHER] = trace_reduce.total(idle) - sum(out.values())
    harness.say("span log: trace origin found from %d step boundaries; "
                "device idle per step, ms: %s"
                % (len(steps), ", ".join(
                    "%s %.3f" % (k, v / len(steps) * 1e3)
                    for k, v in out.items())))
    return dict((k, v / len(steps) * 1e3) for k, v in out.items()), None


def fit_idle_ms(run, phase):
    """Milliseconds per whole ``bench.fit_step`` of the traced window in
    which device 0 ran nothing while the host was inside the logged spans
    named ``phase`` (``OTHER``: inside none of ``FIT_PHASES``). The five
    add up to the device's idle time per step."""
    split = _memo(run, "fit", lambda: _fit_idle(run))
    return None if split is None else split[phase]


# -- serving ------------------------------------------------------------------

def decode_guard(steps, host_window, counted_steps, trace_window):
    """``None`` when the log may be read, else why not."""
    if not host_window or not trace_window:
        return "the run was not traced"
    p0, p1 = host_window
    inside = sum(1 for s in steps if p0 <= s["t1"] < p1)
    if abs(inside - counted_steps) > 2:
        return ("%d decode.step spans logged inside the traced part, the "
                "step histogram counted %d" % (inside, counted_steps))
    w0, w1 = trace_window
    apart = abs((p1 - p0) - (w1 - w0))
    if apart > TOLERANCE_S:
        return ("the traced part is %.3f ms longer on one clock than on "
                "the other" % (apart * 1e3))
    return None


def _decode_window(run):
    log = records()
    iterations = named(log, "decode.iteration")
    if not iterations:
        return None, "the program logs no decode.iteration"
    ids = set(r["span_id"] for r in iterations)
    kids = {}
    for r in log:
        if r["parent_id"] in ids:
            kids.setdefault(r["parent_id"], []).append(r)
    steps = named((k for v in kids.values() for k in v), "decode.step")
    window = None
    if run.trace is not None:
        window = run.trace.annotations.get(trace_reduce.WINDOW_ANNOTATION)
    why = decode_guard(
        steps, run.samples.get("trace_host_window"),
        (run.samples.get("trace_counts") or {}).get("steps", 0),
        window and (min(a for a, _b in window), max(b for _a, b in window)))
    if why is not None:
        return None, why
    due = [r.due for r in run.samples.get("requests", ())]
    if not due:
        return None, "no request was due in the window"
    lo = min(due)
    hi = lo + float(run.samples["window_s"])
    out = []
    for it in iterations:
        if lo <= it["t0"] < hi:
            mine = sorted(kids.get(it["span_id"], ()), key=lambda r: r["t0"])
            out.append((it, mine))
    if not out:
        return None, "no decode.iteration inside the measured window"
    return out, None


def decode_window(run):
    """``[(iteration record, its children oldest first)]`` for the
    engine's passes that began inside the measured window (from the first
    request due, for ``window_s``), or ``None``."""
    return _memo(run, "decode", lambda: _decode_window(run))
