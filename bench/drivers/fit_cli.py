"""Driver ``fit_cli``: the north-star training path through its own entry
point, ``examples/train_imagenet.main(flags, batch_end_callback=...)`` ->
``common/fit.py`` -> ``Module.fit`` -> the fused ``Executor.train_step``,
exactly as ``chip_smoke.run_fit`` calls it.

The window is ONE ``fit`` call. The harness's own ``batch_end_callback``
stamps every step (it fetches the step's outputs for the loss, and that
fetch is the step's sync point), lets ``warmup_steps`` go by as set-up,
then measures until ``--seconds`` have passed and ends the run by raising
``WindowDone`` — nothing in ``base_module.fit`` swallows it, and its
``finally`` closes the iterators. A device sync closes the wall.

Traffic file keys: ``tpus`` (the CLI's ``--tpus``), ``batch_size`` (the
GLOBAL batch, the CLI's ``--batch-size``), ``warmup_steps``, ``flags``
(further CLI flags), ``trace_seconds``, ``reference_rows``,
``reference_tolerance`` (one for each batch-norm mode), and optionally
``rate_metric``: the end-to-end name the rate is reported under where it
is not ``train_samples_per_s`` (a cell whose runs spread too widely for
that metric's bound carries its own). Configuration file key:
``cli_flags`` (network, depth, classes, image shape).
"""
import math
import os
import sys
import time

import numpy as np

from bench import harness, stats, trace_reduce

RATE = "train_samples_per_s"


class WindowDone(Exception):
    """Raised by the callback to end ``fit`` when the window is over."""


def _cross_entropy(mod, batch):
    prob = mod.get_outputs()[0].asnumpy()
    label = batch.label[0].asnumpy().astype(np.int64)
    picked = prob[np.arange(label.shape[0]), label]
    return float(-np.log(np.maximum(picked, 1e-30)).mean())


class Watch(object):
    """``batch_end_callback``. Per step: the loss (a host fetch, so each
    callback is a sync), the host clock, the compile counters."""

    def __init__(self, ctx, mx, telemetry, profiler):
        self.ctx, self.mx, self.tm, self.profiler = ctx, mx, telemetry, profiler
        self.warmup = int(ctx.traffic["warmup_steps"])
        self.trace_seconds = float(ctx.traffic["trace_seconds"])
        self.module = None
        self.steps = []             # dicts, warm-up included
        self.t_begin = None         # first measured instant
        self.t_end = None
        self.real0 = self.disk0 = None
        self.real1 = self.disk1 = None
        self.trace_state = "off" if not ctx.trace else "armed"
        self.trace_path = None
        self._t_trace = None
        self._ann_window = self._ann_step = None
        self._t_prev = time.perf_counter()

    def _compiles(self):
        return (self.tm.counter("programs/compile_total").value,
                self.tm.counter("programs/disk_hits_total").value)

    def _annotate_step(self):
        if self._ann_step is not None:
            self._ann_step.__exit__(None, None, None)
        self._ann_step = self.profiler.TraceAnnotation("bench.fit_step")
        self._ann_step.__enter__()

    def _trace_start(self):
        opts = self.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only; small file
        opts.host_tracer_level = 2
        self.profiler.start_trace(self.ctx.fresh_trace_dir(),
                                  profiler_options=opts)
        self._ann_window = self.profiler.TraceAnnotation(
            trace_reduce.WINDOW_ANNOTATION)
        self._ann_window.__enter__()
        self._annotate_step()
        self._t_trace = time.perf_counter()
        self.trace_state = "on"

    def _trace_stop(self):
        self._ann_step.__exit__(None, None, None)
        self._ann_window.__exit__(None, None, None)
        self._ann_step = self._ann_window = None
        self.profiler.stop_trace()
        self.trace_path = trace_reduce.find_xplane(self.ctx.trace_dir)
        self.trace_state = "done"

    def __call__(self, param):
        mod = param.locals["self"]
        self.module = mod
        loss = _cross_entropy(mod, param.locals["data_batch"])
        now = time.perf_counter()
        n = len(self.steps) + 1
        # phase: "warmup"; "window" (a clean measured step); "traced"
        # (inside the profiler's window); "edge" (holds start/stop_trace)
        phase = "warmup" if self.t_begin is None else "window"
        if self.trace_state == "on":
            phase = "traced"
        self.steps.append({"wall": now - self._t_prev, "loss": loss,
                           "phase": phase})
        if self.t_begin is None:
            if n >= self.warmup:
                self.mx.nd.waitall()
                self.real0, self.disk0 = self._compiles()
                self.t_begin = time.perf_counter()
        else:
            if self.trace_state == "armed" and self._window_steps() >= 2:
                self._trace_start()
                self.steps[-1]["phase"] = "edge"
            elif self.trace_state == "on":
                if now - self._t_trace >= self.trace_seconds:
                    self._trace_stop()
                    self.steps[-1]["phase"] = "edge"
                else:
                    self._annotate_step()
            if time.perf_counter() - self.t_begin >= self.ctx.seconds \
                    and self.trace_state in ("off", "done"):
                self.mx.nd.waitall()
                self.t_end = time.perf_counter()
                self.real1, self.disk1 = self._compiles()
                raise WindowDone()
        self._t_prev = time.perf_counter()

    def _window_steps(self):
        return sum(1 for s in self.steps if s["phase"] != "warmup")


def _fused_programs(programs):
    return sum(1 for r in programs.entries().values()
               if r["kind"] == "fused_step")


def _state_arrays(mx, mod):
    exe = mod._exec
    out = dict(("arg:" + n, a) for n, a in exe.arg_dict.items())
    out.update(("aux:" + n, a) for n, a in exe.aux_dict.items())
    for i, st in mod._updater.states.items():
        for j, a in enumerate(mx.optimizer.fused_state_arrays(st)):
            out["state:%d:%d" % (i, j)] = a
    return out


def _reference_checks(ctx, mx, mod, batch_size):
    """Outside the timing: the module's forward on a seeded batch agrees
    with the plain reference on the same parameters — once as a training
    step runs it (batch-norm by the batch's statistics) and once in
    inference mode (by the moving statistics, the first
    ``reference_rows`` rows).

    The tolerance is on max |p - p_ref| / max p_ref over the softmax
    outputs, and lives in the traffic file. The module multiplies float32
    convolutions in one bf16 pass (JAX's default precision on the TPU: the
    operands rounded to bf16, the sums in float32 — the configuration file
    says so); the reference runs them at ``highest``. What that costs was
    measured on the chip (PERF.md §4) and each tolerance is about twice
    the worst reading. It is there to catch a wrong batch-norm mode, a
    missing or misplaced layer, a wrong layout. It does NOT tell bf16
    activations from what the program does today: with the operands of
    every convolution rounded to bf16 already, keeping the activations in
    bf16 as well moves this statistic only 1.7-fold (the reference against
    itself on the CPU, PERF.md §7), inside any tolerance that the spread
    of the readings allows. The mean entropy is printed so that a saturated
    softmax (every row one-hot, where any arithmetic agrees) shows."""
    import jax
    rows = int(ctx.traffic["reference_rows"])
    image = tuple(int(x) for x in
                  ctx.config["cli_flags"]["image-shape"].split(","))
    rng = np.random.RandomState(ctx.seed % (2 ** 32))
    sample = rng.uniform(-1, 1, (batch_size,) + image).astype(np.float32)
    arg_p, aux_p = mod.get_params()
    params = dict((k, v.asnumpy()) for k, v in arg_p.items())
    params.update((k, v.asnumpy()) for k, v in aux_p.items())
    batch = mx.io.DataBatch(data=[mx.nd.array(sample)], label=None)
    reference = ctx.cell.reference()
    out = []
    # inference first: the training-mode forward moves the statistics
    for name, is_train, n in (("inference_forward", False, rows),
                              ("training_forward", True, batch_size)):
        tol = float(ctx.traffic["reference_tolerance"][name])
        mod.forward(batch, is_train=is_train)
        got = mod.get_outputs()[0].asnumpy()[:n]
        ref = np.asarray(jax.device_get(reference.forward(
            params, sample[:n], batch_stats=is_train)))
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        entropy = float(-(ref * np.log(np.maximum(ref, 1e-30))).sum(1).mean())
        out.append((name + "_agrees_with_reference", err <= tol,
                    "max |p - p_ref| / max p_ref = %.3g over %d rows "
                    "(tolerance %g; mean entropy %.3f nats of %.3f)"
                    % (err, n, tol, entropy, math.log(ref.shape[1]))))
    return out


def run(ctx):
    sys.path.insert(0, os.path.join(ctx.cell.root, "examples"))
    try:
        import jax
        import mxnet_tpu as mx
        import train_imagenet
        from mxnet_tpu import programs, telemetry
    except ImportError as e:
        raise harness.Refused("cannot import the program (%s)" % e)

    tr = ctx.traffic
    batch = int(tr["batch_size"])
    flags = []
    for k, v in sorted(ctx.config["cli_flags"].items()):
        flags += ["--" + k, str(v)]
    for k, v in sorted(tr["flags"].items()):
        flags += ["--" + k, str(v)]
    flags += ["--batch-size", str(batch), "--tpus", str(tr["tpus"])]
    harness.say("train_imagenet.main(%s)" % " ".join(flags))
    harness.say("compile cache: %s" % programs.cache_dir())

    mx.random.seed(ctx.seed)
    fused0 = _fused_programs(programs)
    watch = Watch(ctx, mx, telemetry, jax.profiler)
    try:
        train_imagenet.main(flags, batch_end_callback=watch)
    except WindowDone:
        pass
    if watch.t_end is None:
        raise RuntimeError("fit returned after %d steps, before the window "
                           "ended" % len(watch.steps))
    mod = watch.module
    devs = [mx.tpu(int(i)).jax_device() for i in str(tr["tpus"]).split(",")]
    memory_peak = harness.memory_peak_bytes(devs)
    window = [s for s in watch.steps if s["phase"] != "warmup"]
    wall = watch.t_end - watch.t_begin
    rate = len(window) * batch / wall
    losses = [s["loss"] for s in window]
    harness.say("window: %d steps of %d rows in %.3fs; loss %.4f -> %.4f; "
                "warm-up steps %s s"
                % (len(window), batch, wall, losses[0], losses[-1],
                   ["%.2f" % s["wall"] for s in watch.steps[:watch.warmup]]))
    # a slow run is either slow at every step or stood still in a few:
    # the first shows in the median, the second in what the long steps
    # took beyond it
    walls = [s["wall"] for s in window]
    mid = stats.percentile(walls, 50)
    late = [(i, w - mid) for i, w in enumerate(walls) if w > 1.5 * mid]
    harness.say("steps: p50 %.1f ms, p95 %.1f, max %.1f; %d over 1.5 x p50 "
                "took %.2f s beyond it (step:ms %s); host load %s over %d "
                "cpus"
                % (mid * 1e3, stats.percentile(walls, 95) * 1e3,
                   max(walls) * 1e3, len(late), sum(x for _i, x in late),
                   " ".join("%d:%.0f" % (i, walls[i] * 1e3)
                            for i, _x in late[:8]) or "-",
                   "/".join("%.1f" % x for x in os.getloadavg()),
                   len(os.sched_getaffinity(0))))

    # -- correct --------------------------------------------------------
    checks = []
    fused = _fused_programs(programs) - fused0
    checks.append(("one_fused_step_program", fused == 1,
                   "%d fused_step program(s) registered" % fused))
    devs = set(devs)                # the chips the CLI was told to use
    arrays = _state_arrays(mx, mod)
    stray = [n for n, a in arrays.items()
             if set(a._data.devices()) != devs or not a._data.committed]
    checks.append(("arrays_on_the_cells_chips", not stray,
                   "%d of %d arrays of the step off %s: %s"
                   % (len(stray), len(arrays), sorted(str(d) for d in devs),
                      stray[:4])))
    compiles = (watch.real1 - watch.real0) + (watch.disk1 - watch.disk0)
    checks.append(("zero_compiles_in_window", compiles == 0,
                   "%d real compile(s), %d disk load(s) in the window"
                   % (watch.real1 - watch.real0, watch.disk1 - watch.disk0)))
    bad = [s["loss"] for s in watch.steps if not math.isfinite(s["loss"])]
    checks.append(("loss_finite", not bad,
                   "%d non-finite of %d" % (len(bad), len(watch.steps))))
    # Random weights give every class about the same probability, so the
    # first loss sits near ln(classes): a little above, since Xavier
    # logits have some variance (the smoke saw 7.03 against ln 1000 =
    # 6.91). 0.5 either way still tells 1000 classes from 100 or 10000
    # (ln differs by 2.3) and catches a label/softmax mix-up.
    first = watch.steps[0]["loss"]
    classes = int(ctx.config["cli_flags"]["num-classes"])
    checks.append(("first_loss_near_ln_classes",
                   abs(first - math.log(classes)) <= 0.5,
                   "first loss %.4f, ln(%d) = %.4f"
                   % (first, classes, math.log(classes))))
    checks.append(("loss_falls_on_replayed_batch",
                   losses[-1] < watch.steps[0]["loss"]
                   and losses[-1] < losses[0],
                   "first step %.4f, window %.4f -> %.4f"
                   % (first, losses[0], losses[-1])))
    checks += _reference_checks(ctx, mx, mod, batch)

    return {
        "end_to_end": {tr.get("rate_metric", RATE): rate,
                       "setup_s": watch.t_begin - ctx.t0},
        "attempted": len(window),
        "failed": sum(1 for v in losses if not math.isfinite(v)),
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "trace_path": watch.trace_path,
        "samples": {"steps": window, "warmup": watch.steps[:watch.warmup],
                    "global_batch": batch, "window_s": wall, "rate": rate,
                    "param_shapes": dict(
                        (n, tuple(mod._exec.arg_dict[n].shape))
                        for n in mod._param_names)},
        "counters": {"compiles_in_window": compiles},
    }


def aot_check(cell, hbm, aot):
    """``bench/aot_check.py``: the one-chip fused step at the traffic's
    rows per chip, compiled for a described v5e."""
    import argparse
    import jax
    import mxnet_tpu as mx
    import train_imagenet
    from common import data as exdata
    from mxnet_tpu import health
    one = jax.sharding.SingleDeviceSharding(aot.describe().devices[0])
    chips = len(str(cell.traffic["tpus"]).split(","))
    rows = int(cell.traffic["batch_size"]) // chips
    flags = cell.config["cli_flags"]
    ns = argparse.Namespace(
        network=flags["network"], num_layers=int(flags["num-layers"]),
        num_classes=int(flags["num-classes"]),
        image_shape=flags["image-shape"])
    image = tuple(int(x) for x in ns.image_shape.split(","))
    it = exdata.SyntheticDataIter(ns.num_classes, (rows,) + image, 1,
                                  "float32")
    mod = mx.module.Module(train_imagenet.get_network(ns),
                           context=mx.tpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4})

    def capture(kind, key, prog, args, pkey=None):
        real_backend = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            raise aot.Compiled(prog.lower(*aot.as_shapes(args, one)).compile())
        finally:
            jax.default_backend = real_backend

    health.capture_cost = capture
    try:
        mod.forward_backward(it.next())
        mod.update()
    except aot.Compiled as e:
        compiled = e.args[0]
    else:
        raise SystemExit("the module did not take the fused step")
    text = compiled.as_text()
    print("fused step at %d rows a chip: %d Mosaic custom calls in the "
          "compiled program" % (rows, text.count("tpu_custom_call")))
    return aot.report("fused_step[b%d]" % rows, compiled, hbm)
