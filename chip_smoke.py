#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # on a machine with a TPU
    python chip_smoke.py --tiny-cpu  # rehearsal here: tiny shapes,
                                     # interpret-mode kernels; proves
                                     # nothing about the device

One process, which touches JAX itself and starts no other. Phases:

``train``    the north-star path through its own entry point —
             ``examples/train_imagenet.py`` -> ``common/fit.py`` ->
             ``Module.fit`` -> the fused ``Executor.train_step`` — at full
             width: ResNet-50, 1000 classes, 3x224x224, batch 32, fp32,
             SGD-momentum, kvstore ``device``, one chip, one synthetic
             batch repeated.
``kernels``  every exported Pallas kernel compiled by Mosaic at the
             shapes its production caller uses, against its lax twin
             (``--cases gdn_,hd256`` runs only the cases whose name holds
             one of the parts: a partial run, for a short chip budget).
``trace_clock``  a profiler trace around three fused steps of the same
             path: the program's spans are events of the trace's host
             plane; prints which Python clock that plane is on, and holds
             each event to its span in ``tracing.span_log()`` to 0.2 ms.
``dp4``      the same path data-parallel over four chips (global batch
             128), when four are visible; otherwise reported as skipped.

Each phase prints one status line. Any failure raises: no phase catches
an exception and carries on. The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, with the device as JAX
reports it. Exit code 0 only when every phase that could run passed, on an
accelerator (or in the explicit rehearsal, whose verdict says so). Walls
are printed as information; no number here is a measurement claim.
"""
import argparse
import glob
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("train", "kernels", "trace_clock", "dp4")


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="rehearse on CPU at tiny shapes with interpreted "
                         "kernels (proves nothing about the device)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list of phases to run (debugging aid that "
                         "saves chip time; a partial run never prints the "
                         "final ok verdict)")
    ap.add_argument("--cases", default="",
                    help="comma list of name parts: the kernels phase runs "
                         "only the cases whose name holds one (a cold run "
                         "of all of them outlasts 13 minutes); like "
                         "--phases it makes the run partial: no verdict")
    args = ap.parse_args()
    args.phases = args.phases.split(",")
    args.cases = [c for c in args.cases.split(",") if c]
    unknown = set(args.phases) - set(PHASES)
    if unknown:
        ap.error("unknown phase(s): %s" % sorted(unknown))
    return args


ARGS = _parse()
if ARGS.tiny_cpu:
    # explicit rehearsal: force the platform BEFORE jax is imported, with
    # enough virtual host devices for the dp4 phase
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "examples"))
try:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    import train_imagenet
except ImportError as e:
    sys.exit("chip_smoke: cannot import the program next to this script "
             "(%s); run it from a checkout of the repository" % e)

from mxnet_tpu import programs, telemetry, tracing           # noqa: E402

TINY = ARGS.tiny_cpu


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def status(phase, verdict, **info):
    print("[chip_smoke] phase %-8s %s %s"
          % (phase, verdict, json.dumps(info, sort_keys=True)), flush=True)


# ---------------------------------------------------------------------------
# phase 0: the device
# ---------------------------------------------------------------------------

def device_phase():
    devs = jax.devices()
    d0 = devs[0]
    print("[chip_smoke] device platform=%s device_kind=%r count=%d"
          % (d0.platform, d0.device_kind, len(devs)), flush=True)
    print("[chip_smoke] compile cache dir: %s" % programs.cache_dir(),
          flush=True)
    if TINY:
        print("[chip_smoke] REHEARSAL (--tiny-cpu): tiny shapes on the host "
              "CPU, kernels interpreted. This run proves NOTHING about the "
              "device.", flush=True)
    elif d0.platform == "cpu":
        sys.exit("chip_smoke: JAX found no accelerator (platform 'cpu'). "
                 "This check only means something on the chip; to rehearse "
                 "the command here pass --tiny-cpu.")
    return devs


# ---------------------------------------------------------------------------
# phase train / dp4: the CLI's own main(), watched through a callback
# ---------------------------------------------------------------------------

def _cross_entropy(mod, batch):
    """Mean cross-entropy of ``batch`` from the module's softmax output
    (fetched to the host)."""
    prob = mod.get_outputs()[0].asnumpy()
    label = batch.label[0].asnumpy().astype(np.int64)
    picked = prob[np.arange(label.shape[0]), label]
    return float(-np.log(np.maximum(picked, 1e-30)).mean())


class StepWatch(object):
    """batch_end_callback: per step, the loss of the batch just trained
    on (fetched to the host — that fetch is the step's sync point), the
    wall since the previous step ended, and the compile-request count."""

    def __init__(self):
        self.loss, self.wall, self.compiles = [], [], []
        self.module = None
        self._t = time.perf_counter()

    def __call__(self, param):
        mod = param.locals["self"]
        batch = param.locals["data_batch"]
        self.loss.append(_cross_entropy(mod, batch))
        now = time.perf_counter()
        self.wall.append(now - self._t)
        self._t = now
        self.compiles.append(telemetry.compile_count())
        self.module = mod


def _model(batch):
    """The network of both training phases at ``batch`` rows: full-width
    ResNet-50, or the tiny rehearsal stand-in (a quarter of the rows)."""
    if TINY:
        return {"network": "resnet", "num_layers": 8, "num_classes": 10,
                "image_shape": "3,16,16", "batch_size": batch // 4}
    return {"network": "resnet", "num_layers": 50, "num_classes": 1000,
            "image_shape": "3,224,224", "batch_size": batch}


# steps 1-2 are warm-up: pjit keeps one executable per input provenance
# (fresh device_put arrays on step 1, the program's own outputs from
# step 2 on — programs.warm_twice), so "zero compiles" is asserted from
# step 3
WARMUP_STEPS = 2
STEPS = 8


def run_fit(tpus, batch, watch=None):
    """``python examples/train_imagenet.py --benchmark 1 ...`` in this
    process. Returns (watch, real_compiles, disk_hits, fused programs
    added)."""
    flags = []
    for k, v in _model(batch).items():
        flags += ["--" + k.replace("_", "-"), str(v)]
    flags += [
        "--benchmark", "1", "--num-epochs", "1", "--max-batches", str(STEPS),
        "--kv-store", "device", "--optimizer", "sgd", "--lr", "0.05",
        "--mom", "0.9", "--wd", "1e-4", "--disp-batches", "4",
        "--tpus", tpus]
    mx.random.seed(0)
    fused0 = _fused_programs()
    real0 = telemetry.counter("programs/compile_total").value
    disk0 = telemetry.counter("programs/disk_hits_total").value
    watch = watch or StepWatch()
    train_imagenet.main(flags, batch_end_callback=watch)
    return (watch,
            telemetry.counter("programs/compile_total").value - real0,
            telemetry.counter("programs/disk_hits_total").value - disk0,
            _fused_programs() - fused0)


def _fused_programs():
    return sum(1 for r in programs.entries().values()
               if r["kind"] == "fused_step")


def _check_steps(phase, watch, fused_added):
    check(len(watch.loss) == STEPS,
          "%s: %d of %d steps ran" % (phase, len(watch.loss), STEPS))
    check(all(math.isfinite(v) for v in watch.loss),
          "%s: non-finite loss %s" % (phase, watch.loss))
    check(watch.loss[-1] < watch.loss[0],
          "%s: loss did not fall on a repeated batch: %s"
          % (phase, watch.loss))
    check(fused_added == 1,
          "%s: %d fused_step programs registered, expected exactly 1 (the "
          "unfused replay registers none, a retrace more than one)"
          % (phase, fused_added))
    late = watch.compiles[-1] - watch.compiles[WARMUP_STEPS - 1]
    check(late == 0, "%s: %d compile request(s) after the warm-up steps "
          "(per-step totals %s)" % (phase, late, watch.compiles))


def _state_arrays(mod):
    """Every array the step owns: parameters + staged batch, aux states,
    optimizer state."""
    exe = mod._exec
    out = dict(("arg:" + n, a) for n, a in exe.arg_dict.items())
    out.update(("aux:" + n, a) for n, a in exe.aux_dict.items())
    for i, st in mod._updater.states.items():
        for j, a in enumerate(mx.optimizer.fused_state_arrays(st)):
            out["state:%d:%d" % (i, j)] = a
    return out


def _hbm_in_use(dev):
    stats = dev.memory_stats()
    if stats is None:
        check(TINY, "device %s reports no memory_stats" % dev)
        return None              # host CPU backend in the rehearsal
    return int(stats["bytes_in_use"])


def _mosaic_in(fn, *args):
    """Does ``fn`` lower to a Mosaic custom call when traced (tracer
    inputs: the dispatch every jitted caller sees)?"""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def train_phase():
    # In the rehearsal "the chip" is host device 1, so that anything left
    # on the default device 0 (where cpu(0)-context arrays live) shows up
    # exactly like a host-resident array would on a TPU machine.
    dev_id = 1 if TINY else 0
    dev = mx.tpu(dev_id).jax_device()
    t0 = time.perf_counter()
    watch, real, disk, fused_added = run_fit(str(dev_id), 32)
    wall = time.perf_counter() - t0
    _check_steps("train", watch, fused_added)
    mod = watch.module
    arrays = _state_arrays(mod)
    stray = dict((n, sorted(str(d) for d in a._data.devices()))
                 for n, a in arrays.items()
                 if a._data.devices() != {dev} or not a._data.committed)
    check(not stray, "train: %d of %d arrays are not committed to %s: %s"
          % (len(stray), len(arrays), dev, dict(list(stray.items())[:6])))
    nbytes = sum(a._data.nbytes for a in arrays.values())
    hbm = _hbm_in_use(dev)
    check(hbm is None or hbm >= nbytes,
          "train: device reports %s bytes in use, the step's arrays alone "
          "are %d" % (hbm, nbytes))
    # the update rule the step traced is plain XLA: an elementwise fusion
    # over each parameter in the layout it has, no custom call to feed
    rule = mod._optimizer.fused_rule()
    idx = len(mod._param_names) - 1
    w = mod._exec.arg_dict[mod._param_names[idx]]._data
    st = tuple(a._data for a in mx.optimizer.fused_state_arrays(
        mod._updater.states[idx]))
    hyper = mod._optimizer.fused_hyper(idx)
    check(not _mosaic_in(rule, w, w, st, hyper),
          "train: update rule %s lowers to a Mosaic kernel on platform %s"
          % (rule.__name__, dev.platform))
    steady = sorted(watch.wall[WARMUP_STEPS:])
    status("train", "passed", arrays=len(arrays), on_device=str(dev),
           state_mbytes=round(nbytes / 1e6, 1),
           hbm_in_use_mbytes=None if hbm is None else round(hbm / 1e6, 1),
           loss_first=round(watch.loss[0], 4),
           loss_last=round(watch.loss[-1], 4),
           first_step_wall_s=round(watch.wall[0], 2),
           steady_ms_per_step=round(steady[len(steady) // 2] * 1e3, 2),
           phase_wall_s=round(wall, 2), real_compiles=real, disk_hits=disk,
           step_program="loaded from the compile cache" if real == 0
           else "compiled", update_rule=rule.__name__)


# ---------------------------------------------------------------------------
# phase kernels
# ---------------------------------------------------------------------------

def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12))


def kernels_phase():
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
    i8 = importlib.import_module("mxnet_tpu.ops.pallas.int8_matmul")
    mf = importlib.import_module("mxnet_tpu.ops.pallas.moe_ffn")
    ml = importlib.import_module("mxnet_tpu.ops.pallas.mla_attention")
    gd = importlib.import_module("mxnet_tpu.ops.pallas.gated_delta")
    mb = importlib.import_module("mxnet_tpu.ops.pallas.mamba")
    da = importlib.import_module("mxnet_tpu.ops.pallas.diff_attention")
    rng = np.random.RandomState(0)
    # on the chip: interpret=None, the production default, which must
    # resolve to Mosaic (asserted per kernel through _mosaic_in); in the
    # rehearsal: the interpreter, which runs the same kernel bodies
    interp = True if TINY else None
    report = {}

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    def case(name, kernel, twin, args, tol, exact=False):
        if ARGS.cases and not any(c in name for c in ARGS.cases):
            return
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel(*args))
        wall = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = twin(*args)
        errs = [_rel_err(g, r) for g, r in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref))]
        if exact:
            same = all(np.array_equal(np.asarray(g), np.asarray(r))
                       for g, r in zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(ref)))
            check(same, "kernels: %s is not bit-equal to its twin" % name)
        check(max(errs) <= tol, "kernels: %s differs from its twin by %s "
              "(tolerance %g)" % (name, errs, tol))
        if not TINY:
            check(_mosaic_in(kernel, *args),
                  "kernels: %s did not lower to a Mosaic kernel" % name)
        report[name] = {"max_rel_err": max(errs),
                        "first_call_s": round(wall, 2)}

    # -- flash attention: train_transformer_lm shapes (b8, 16 heads, seq
    #    1024, head_dim 64), bf16, and head_dim 128
    flash = [(1, 2, 32, 16, jnp.float32)] if TINY else \
        [(8, 16, 1024, 64, jnp.float32), (8, 16, 1024, 64, jnp.bfloat16),
         (2, 8, 1024, 128, jnp.bfloat16)]
    for b, h, s, d, dt in flash:
        q, k, v = (jnp.asarray(rng.randn(b, h, s, d), dt) for _ in range(3))
        case("flash_attention[b%dh%ds%dd%d,%s]" % (b, h, s, d, dt.__name__),
             lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                interpret=interp),
             lambda q, k, v, d=d: fa._flash_fwd_xla(q, k, v, True,
                                                    1.0 / d ** 0.5)[0],
             (q, k, v), 2e-2)

    # -- paged decode attention: page_size 16, GQA groups of 4 and 2 (heads
    #    of whole lane tiles: a narrower head is served by the twin, below)
    paged = [(2, 2, 2, 8, 4, 8, 2)] if TINY else \
        [(8, 2, 4, 128, 16, 512, 16), (8, 4, 2, 128, 16, 512, 16)]
    for b, kvh, g, hd, ps, npages, ppseq in paged:
        q = f32(b, kvh, g, hd)
        kp, vp = f32(npages, ps, kvh, hd), f32(npages, ps, kvh, hd)
        bt = jnp.asarray(1 + rng.permutation(npages - 1)[:b * ppseq]
                         .reshape(b, ppseq), jnp.int32)
        ln = jnp.asarray(rng.randint(1, ps * ppseq + 1, size=(b,)),
                         jnp.int32)
        case("paged_decode_attention[b%dkvh%dg%dhd%d]" % (b, kvh, g, hd),
             lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
                 q, kp, vp, bt, ln, interpret=interp),
             lambda q, kp, vp, bt, ln, hd=hd: fa._paged_decode_xla(
                 q, kp, vp, bt, ln, 1.0 / hd ** 0.5),
             (q, kp, vp, bt, ln), 2e-2)

    if not TINY:
        narrow = (f32(8, 2, 4, 64), f32(64, 16, 2, 64), f32(64, 16, 2, 64),
                  jnp.asarray(1 + np.arange(32).reshape(8, 4), jnp.int32),
                  jnp.asarray(rng.randint(1, 65, size=(8,)), jnp.int32))
        check(not _mosaic_in(fa.paged_decode_attention, *narrow),
              "kernels: paged_decode_attention at head_dim 64 did not "
              "dispatch to its twin")
        jax.block_until_ready(fa.paged_decode_attention(*narrow))

    # -- the same against a RING of block-table entries under a window
    #    (rows not wrapped, wrapped once and several times)
    b, kvh, g, hd, ps, ring, window = (3, 2, 2, 8, 4, 3, 8) if TINY else \
        (8, 4, 7, 128, 16, 17, 256)
    q = f32(b, kvh, g, hd)
    kp, vp = f32(b * ring + 1, ps, kvh, hd), f32(b * ring + 1, ps, kvh, hd)
    bt = jnp.asarray(1 + np.arange(b * ring).reshape(b, ring), jnp.int32)
    ln = jnp.asarray(rng.randint(1, 6 * window, size=(b,)), jnp.int32)
    case("paged_decode_attention[window%d,ring%d]" % (window, ring),
         lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
             q, kp, vp, bt, ln, interpret=interp, window=window),
         lambda q, kp, vp, bt, ln: fa._paged_decode_xla(
             q, kp, vp, bt, ln, 1.0 / hd ** 0.5, window),
         (q, kp, vp, bt, ln), 2e-2)

    # -- flash prefill with the fused page write
    prefill = [(2, 16, 4, 2, 8, 4)] if TINY else \
        [(2, 128, 8, 2, 64, 16), (2, 256, 8, 4, 128, 16)]
    for b, s, nh, kvh, hd, ps in prefill:
        q = f32(b, s, nh, hd)
        kg, vg = f32(b, s, kvh, hd), f32(b, s, kvh, hd)
        npages = b * (s // ps) + 1
        kp = jnp.zeros((npages, ps, kvh, hd), jnp.float32)
        vp = jnp.zeros((npages, ps, kvh, hd), jnp.float32)
        bt = jnp.asarray(np.arange(1, npages).reshape(b, s // ps),
                         jnp.int32)
        case("flash_prefill_paged[b%ds%dnh%dkvh%dhd%d]"
             % (b, s, nh, kvh, hd),
             lambda *a: fa.flash_prefill_paged(*a, interpret=interp),
             fa._flash_prefill_xla, (q, kg, vg, kp, vp, bt), 2e-2)

    # -- ... by the rows' real lengths onto a ring, under a window
    b, s, nh, kvh, hd, ps, ring, window = (2, 32, 4, 2, 8, 4, 3, 8) \
        if TINY else (2, 1024, 28, 4, 128, 16, 17, 256)
    q = f32(b, s, nh, hd)
    kg, vg = f32(b, s, kvh, hd), f32(b, s, kvh, hd)
    kp, vp = (jnp.zeros((b * ring + 1, ps, kvh, hd), jnp.float32)
              for _ in range(2))
    bt = jnp.asarray(1 + np.arange(b * ring).reshape(b, ring), jnp.int32)
    ln = jnp.asarray([s - 2 * ps - 3, ps + 2], jnp.int32)

    def real_pages(out):
        # page 0 takes whatever is not kept, in any order
        return (out[0],) + tuple(p[1:] for p in out[1:])

    case("flash_prefill_paged[window%d,ring%d]" % (window, ring),
         lambda *a: real_pages(fa.flash_prefill_paged(
             *a[:6], interpret=interp, lengths=a[6], window=window)),
         lambda *a: real_pages(fa._flash_prefill_xla(*a, window)),
         (q, kg, vg, kp, vp, bt, ln), 2e-2)

    # -- both paged kernels over the WHOLE pool of a kind of layer, at a
    #    layer that is not the first — how the serving programs call them
    #    (no pool[layer] slice: a copy in front of a custom call). Shapes
    #    of both served cells: 24 layers of 16 KV heads under 32 slots of
    #    2048 tokens; 9 window layers of 4 KV heads, window 4096 on rings
    #    of 257 entries. Pools drawn on the device, fewer pages than served
    whole = [(3, 2, 2, 2, 8, 4, 2, 9, None, 1),
             (3, 3, 2, 2, 8, 4, 3, 12, 8, 2)] if TINY else \
        [(24, 32, 16, 1, 128, 16, 128, 513, None, 23),
         (9, 32, 4, 7, 128, 16, 257, 4096, 4096, 8),
         # ... and its global layers: 1024 table entries a row, of which
         # the walk visits the written ones, 16 pages a block
         (3, 32, 4, 7, 128, 16, 1024, 2049, None, 2),
         # a linear-attention model's 3 full layers: 2 KV heads of 256
         # serving 8 query heads each, pages of 512 tokens
         (3, 32, 2, 8, 256, 512, 32, 129, None, 2)]
    dt = jnp.float32 if TINY else jnp.bfloat16
    for layers, b, kvh, g, hd, ps, ppseq, npages, window, layer in whole:
        keys = jax.random.split(jax.random.PRNGKey(layer), 3)
        q = jax.random.normal(keys[0], (b, kvh, g, hd), dt)
        kp, vp = (jax.random.normal(k, (layers, npages, ps, kvh, hd), dt)
                  for k in keys[1:])
        bt = jnp.asarray(rng.randint(1, npages, size=(b, ppseq)), jnp.int32)
        ln = jnp.asarray(rng.randint(1, (6 if window else 1) * ps * ppseq
                                     + 1, size=(b,)), jnp.int32)
        case("paged_decode_attention[layer%dof%d,b%dkvh%dg%dhd%d,window%s]"
             % (layer, layers, b, kvh, g, hd, window),
             lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
                 q, kp, vp, bt, ln, interpret=interp, window=window,
                 layer=layer),
             lambda q, kp, vp, bt, ln: fa._paged_decode_xla(
                 q, kp, vp, bt, ln, 1.0 / hd ** 0.5, window, layer),
             (q, kp, vp, bt, ln), 2e-2)

    whole = [(3, 16, 4, 2, 8, 4, 4, None, 1),
             (3, 32, 4, 2, 128, 4, 3, 8, 2)] if TINY else \
        [(24, 2048, 16, 16, 128, 16, 128, None, 23),
         (9, 4608, 28, 4, 128, 16, 257, 4096, 8),
         (3, 2048, 16, 2, 256, 512, 4, None, 2)]
    for layers, s, nh, kvh, hd, ps, entries, window, layer in whole:
        keys = jax.random.split(jax.random.PRNGKey(100 + layer), 5)
        q = jax.random.normal(keys[0], (1, s, nh, hd), dt)
        kg, vg = (jax.random.normal(k, (1, s, kvh, hd), dt)
                  for k in keys[1:3])
        kp, vp = (jax.random.normal(k, (layers, entries + 1, ps, kvh, hd),
                                    dt) for k in keys[3:])
        bt = jnp.asarray(1 + np.arange(entries).reshape(1, entries),
                         jnp.int32)
        ln = None if window is None else jnp.asarray([s - ps - 3], jnp.int32)

        def layer_and_rest(out, kp=kp, vp=vp, layer=layer):
            # the layer's real pages (page 0 takes what is not kept), and
            # whether every OTHER layer came back as it went in
            rest = [jnp.asarray(jnp.array_equal(
                jnp.delete(new, layer, 0), jnp.delete(old, layer, 0)),
                jnp.float32) for new, old in zip(out[1:], (kp, vp))]
            return [out[0]] + [p[layer, 1:] for p in out[1:]] + rest

        case("flash_prefill_paged[layer%dof%d,s%dnh%dkvh%dhd%d,window%s]"
             % (layer, layers, s, nh, kvh, hd, window),
             lambda *a: layer_and_rest(fa.flash_prefill_paged(
                 *a, interpret=interp, lengths=ln, window=window,
                 layer=layer)),
             lambda *a: layer_and_rest(fa._flash_prefill_xla(
                 *a, ln, window, layer)),
             (q, kg, vg, kp, vp, bt), 2e-2)

    # -- grouped expert FFN: a decode step's tile of 16 and a prefill's of
    #    128, rows sorted by expert, one expert left without a row
    from mxnet_tpu.parallel.moe import sorted_dispatch, top_k_routing
    moe = [(24, 32, 16, 8, 3, 16)] if TINY else \
        [(32, 2560, 768, 64, 6, 16), (2048, 2560, 768, 64, 6, 128)]
    for n, d, f, e, k, tile in moe:
        dt = jnp.float32 if TINY else jnp.bfloat16
        wg, wu = (jnp.asarray(rng.randn(1, 2, e, d, f) * 0.02, dt)
                  for _ in range(2))
        wd = jnp.asarray(rng.randn(1, 2, e, f, d) * 0.02, dt)
        logits = f32(n, e).at[:, 1].add(-50.0)
        src, _dest, sizes, _counts = sorted_dispatch(
            top_k_routing(logits, k)[0], e, tile)
        rows = jnp.asarray(rng.randn(n, d), dt)[src]
        used = int(sizes.sum())          # rows past it are never written

        def twin(r, gs, a, b_, c, u=used):
            # bf16 products are exact in one pass; XLA:TPU's ragged_dot
            # refuses "highest" over bf16 operands ("Bad lhs type")
            with jax.default_matmul_precision("bfloat16"):
                return mf._moe_grouped_ffn_xla(r, gs, a, b_, c,
                                               (0, 1))[:u]

        case("moe_grouped_ffn[n%dtile%d]" % (n, tile),
             lambda r, gs, a, b_, c, t=tile, u=used: mf.moe_grouped_ffn(
                 r, gs, a, b_, c, t, interpret=interp, lead=(0, 1))[:u],
             twin, (rows, sizes, wg, wu, wd), 2e-2)

    # -- ... with f TILED (an expert of 7168 x 2048 does not fit VMEM) and
    #    a SiLU gate: 16 held experts, a decode step's rows, layer 1 of 2
    n, d, f, e, tile = (24, 32, 256, 3, 16) if TINY else \
        (64, 7168, 2048, 16, 128)
    dt = jnp.float32 if TINY else jnp.bfloat16
    wg, wu = (jnp.asarray(rng.randn(1, 2, e, d, f) * 0.02, dt)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(1, 2, e, f, d) * 0.02, dt)
    chosen = jnp.asarray(rng.randint(0, e + 5, (n, 8)), jnp.int32)
    src, _dest, sizes, _counts = sorted_dispatch(chosen, e, tile, first=2)
    rows = jnp.asarray(rng.randn(n, d), dt)[src]
    used = int(sizes.sum())

    def tiled_twin(r, gs, a, b_, c, u=used):
        with jax.default_matmul_precision("bfloat16"):
            return mf._moe_grouped_ffn_xla(r, gs, a, b_, c, (0, 1),
                                           "silu")[:u]

    case("moe_grouped_ffn[f-tiled,silu,n%dtile%d]" % (n, tile),
         lambda r, gs, a, b_, c, u=used: mf.moe_grouped_ffn(
             r, gs, a, b_, c, tile, interpret=interp, lead=(0, 1),
             act="silu", tf=128 if TINY else None)[:u],
         tiled_twin, (rows, sizes, wg, wu, wd), 2e-2)

    # -- latent attention (MLA): the absorbed decode over a whole paged
    #    pool by layer index, and the decompressed causal prefill
    b, heads, rank, rope, ps, entries, layers = (3, 4, 128, 64, 8, 4, 2) \
        if TINY else (16, 128, 512, 64, 512, 16, 3)
    q = jnp.asarray(rng.randn(b, heads, rank + rope), dt)
    pages = jnp.asarray(rng.randn(*ml.latent_pool_shape(
        layers, b * entries + 1, ps, rank + rope)), dt)
    bt = jnp.asarray(1 + rng.permutation(b * entries).reshape(b, entries),
                     jnp.int32)
    ln = jnp.asarray(rng.randint(1, entries * ps, size=(b,)), jnp.int32)
    scale = (128 + rope) ** -0.5 * 1.874
    case("mla_paged_decode[layer%dof%d,b%dh%d,page%d]"
         % (layers - 1, layers, b, heads, ps),
         lambda q, pages, bt, ln: ml.mla_paged_decode(
             q, pages, bt, ln, scale, rank, interpret=interp,
             layer=layers - 1),
         lambda q, pages, bt, ln: ml._mla_paged_decode_xla(
             q, pages, bt, ln, scale, rank, layers - 1),
         (q, pages, bt, ln), 2e-2)
    b, heads, s, dn, dv = (2, 3, 32, 16, 16) if TINY else (1, 16, 2048, 128,
                                                           128)
    rope = 8 if TINY else 64
    arr = lambda *shape: jnp.asarray(rng.randn(*shape), dt)
    case("mla_flash_prefill[b%dh%ds%d]" % (b, heads, s),
         lambda *a: ml.mla_flash_prefill(
             *a, scale, interpret=interp, block_q=8 if TINY else 512,
             block_k=8 if TINY else 512),
         lambda *a: ml._mla_flash_prefill_xla(*a, scale),
         (arr(b, heads, s, dn), arr(b, heads, s, rope),
          arr(b, heads, s, dn), arr(b, s, rope), arr(b, heads, s, dv)),
         2e-2)

    # -- Gated DeltaNet (linear attention): one token a row against rows of
    #    a whole state pool, in place by layer and state row (two rows
    #    share the null row 0, as dummy slots do); a prompt in chunks of 64
    #    whose length is no multiple of the chunk, with a padded tail
    b, kh, vh, width, layers, pool_rows = (3, 1, 8, 128, 2, 4) if TINY \
        else (16, 16, 32, 128, 3, 17)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    pool = f32(layers, pool_rows, vh, width, width)
    rows = jnp.asarray([0, 0] + list(range(1, b - 1)), jnp.int32)
    args = (unit(f32(b, kh, width)) * width ** -0.5, unit(f32(b, kh, width)),
            f32(b, vh, width).astype(dt), -jnp.exp(f32(b, vh)),
            jax.nn.sigmoid(f32(b, vh)), pool, rows)

    def real_rows(out):
        # the null row takes whichever of its writers came last
        return out[0][2:], out[1][:, 1:]

    case("gdn_recurrent_step[layer%dof%d,b%dvh%dd%d]"
         % (layers - 1, layers, b, vh, width),
         lambda *a: real_rows(gd.gdn_recurrent_step(
             *a, layer=layers - 1, interpret=interp)),
         lambda *a: real_rows(gd._gdn_recurrent_xla(*a, layers - 1)),
         args, 1e-4)
    s, real = (100, 71) if TINY else (2048, 1999)
    kh, vh = (1, 2) if TINY else (16, 32)
    live = (jnp.arange(s) < real)[None, :, None]
    args = (unit(f32(1, s, kh, width)) * width ** -0.5,
            unit(f32(1, s, kh, width)), f32(1, s, vh, width).astype(dt),
            jnp.where(live, -jnp.exp(f32(1, s, vh) * 2.0), 0.0),
            jnp.where(live, jax.nn.sigmoid(f32(1, s, vh)), 0.0))
    case("gdn_chunk_prefill[s%dkh%dvh%dd%d]" % (s, kh, vh, width),
         lambda *a: (lambda o, st: (o[:, :real], st))(
             *gd.gdn_chunk_prefill(*a, interpret=interp)),
         lambda *a: (lambda o, st: (o[:, :real], st))(
             *gd._gdn_chunk_xla(*a)),
         args, 2e-3)

    # -- Mamba's selective scan: one token a row against rows of a whole
    #    state pool, in place by layer and state row (two rows share the
    #    null row 0); a prompt in chunks whose length is no multiple of the
    #    chunk, its padded tail at step size 0
    b, ch, ns, layers, pool_rows = (3, 128, 4, 2, 4) if TINY \
        else (64, 5120, 16, 9, 65)
    rows = jnp.asarray([0, 0] + list(range(1, b - 1)), jnp.int32)
    decay = -jnp.exp(f32(ns, ch) * 0.5)
    args = (f32(b, ch), jnp.abs(f32(b, ch)) * 0.1, f32(b, ns), f32(b, ns),
            decay, f32(ch), f32(layers, pool_rows, ns, ch), rows)
    case("mamba_recurrent_step[layer%dof%d,b%dn%dch%d]"
         % (layers - 1, layers, b, ns, ch),
         lambda *a: real_rows(mb.mamba_recurrent_step(
             *a, layer=layers - 1, interpret=interp)),
         lambda *a: real_rows(mb._mamba_step_xla(*a, layers - 1)),
         args, 1e-4)
    s, real = (100, 71) if TINY else (2048, 1999)
    live = (jnp.arange(s) < real)[None, :, None]
    args = (f32(1, s, ch), jnp.where(live, jnp.abs(f32(1, s, ch)) * 0.1,
                                     0.0), f32(1, s, ns), f32(1, s, ns),
            decay, f32(ch))
    case("mamba_chunk_scan[s%dn%dch%d]" % (s, ns, ch),
         lambda *a: (lambda y, st: (y[:, :real], st))(
             *mb.mamba_chunk_scan(*a, interpret=interp)),
         lambda *a: (lambda y, st: (y[:, :real], st))(
             *mb._mamba_scan_xla(*a)),
         args, 1e-3)

    # -- differential attention over flat pages of K/V pairs: the one full
    #    layer's pages and a window layer's rings of a whole pool, by layer;
    #    a prompt's both maps, causal and windowed
    b, pairs, ps, entries = (3, 2, 8, 6) if TINY else (32, 10, 128, 16)
    window = 16 if TINY else 512
    ring = window // ps + 1
    for name, layers, width, win, most in (
            ("full", 1, entries, None, entries * ps),
            ("window", 3 if TINY else 8, ring, window, entries * ps)):
        n_pages = b * width + 1          # a page a table entry, and null
        pool = lambda: f32(layers, n_pages, ps * pairs, 128).astype(dt)
        table = jnp.asarray(rng.randint(1, n_pages, (b, width)), jnp.int32)
        lengths = jnp.asarray(rng.randint(1, most + 1, b), jnp.int32)
        args = (f32(b, pairs, 4, 128), pool(), pool(), table, lengths)
        case("diff_paged_decode[%s,layer%dof%d,b%dpairs%dpage%d]"
             % (name, layers - 1, layers, b, pairs, ps),
             lambda *a, win=win, layers=layers: da.diff_paged_decode(
                 *a, ps, 0.125, window=win, layer=layers - 1,
                 interpret=interp),
             lambda *a, win=win, layers=layers: da._diff_paged_decode_xla(
                 *a, 0.125, win, layers - 1),
             args, 2e-2)
    s = 64 if TINY else 2048
    for win in (None, window):
        args = (f32(1, s, 4 * pairs, 128).astype(dt),
                f32(1, s, pairs, 128).astype(dt),
                f32(1, s, pairs, 128).astype(dt))
        case("diff_flash_prefill[s%dpairs%d,window%s]" % (s, pairs, win),
             lambda *a, win=win: da.diff_flash_prefill(
                 *a, 0.125, win, interpret=interp),
             lambda *a, win=win: da._diff_prefill_xla(*a, 0.125, win),
             args, 2e-2)

    # -- int8 matmul + im2col conv: ResNet-50's FC and its first 3x3 conv
    mm = [(8, 40, 12)] if TINY else [(32, 2048, 1000),
                                    (32 * 56 * 56, 576, 64)]
    for m_, k_, n_ in mm:
        x = jnp.asarray(rng.randint(-127, 128, (m_, k_)), jnp.int8)
        wq = jnp.asarray(rng.randint(-127, 128, (n_, k_)), jnp.int8)
        sc = jnp.asarray(rng.rand(n_), jnp.float32)
        case("int8_matmul[%dx%dx%d]" % (m_, k_, n_),
             lambda x, wq, sc: i8.int8_matmul(x, wq, sc, interpret=interp),
             i8._int8_matmul_xla, (x, wq, sc), 0.0, exact=True)
    cb, cc, chw = (2, 4, 8) if TINY else (32, 64, 56)
    qx = jnp.asarray(rng.randint(-127, 128, (cb, cc, chw, chw)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (cc, cc, 3, 3)), jnp.int8)
    sc = jnp.asarray(rng.rand(cc), jnp.float32)
    conv_args = ((1, 1), (1, 1), (1, 1))
    case("int8_conv_im2col[b%dc%d,%dx%d,3x3]" % (cb, cc, chw, chw),
         lambda qx, wq, sc: i8.int8_conv_im2col(qx, wq, sc, *conv_args,
                                                interpret=interp),
         lambda qx, wq, sc: i8._int8_conv_xla(qx, wq, sc, *conv_args, 1),
         (qx, wq, sc), 0.0, exact=True)

    exported = set()
    for mod in (fa, i8, mf, ml, gd, mb, da):
        exported.update(mod.PALLAS_KERNELS)
    covered = set(n.split("[")[0] for n in report)
    check(ARGS.cases or covered == exported,
          "kernels: exported %s, exercised %s"
          % (sorted(exported), sorted(covered)))
    status("kernels", "passed", cases=len(report),
           mode="interpret (rehearsal)" if TINY else "mosaic",
           worst_rel_err=max(r["max_rel_err"] for r in report.values()),
           kernels=sorted(exported))
    for name in sorted(report):
        print("[chip_smoke]   %-48s %s" % (name, json.dumps(report[name])),
              flush=True)


# ---------------------------------------------------------------------------
# phase trace_clock: the program's spans on the device trace's clock
# ---------------------------------------------------------------------------

TRAIN_SPANS = ("train.step", "train.forward_backward", "train.update",
               "executor.stage_input", "executor.train_step",
               "train.update_metric", "train.data_wait", "train.callbacks")
PROBE = "chip_smoke.clock_probe"
TRACED_STEPS = 3
CLOCKS = (("time.time_ns", time.time_ns),
          ("time.monotonic_ns", time.monotonic_ns),
          ("time.perf_counter_ns", time.perf_counter_ns))


class TraceWatch(StepWatch):
    """Starts a profiler trace in the callback that ends the warm-up and
    stops it ``TRACED_STEPS`` callbacks later; in between, every callback
    stamps the three Python clocks and opens one probe annotation."""

    def __init__(self, trace_dir):
        StepWatch.__init__(self)
        self.trace_dir = trace_dir
        self.stamps = []

    def __call__(self, param):
        StepWatch.__call__(self, param)
        n = len(self.loss)
        if n == WARMUP_STEPS + 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        elif WARMUP_STEPS + 1 < n <= WARMUP_STEPS + 1 + TRACED_STEPS:
            self.stamps.append(tuple(clock() for _name, clock in CLOCKS))
            with jax.profiler.TraceAnnotation(PROBE):
                pass
            if n == WARMUP_STEPS + 1 + TRACED_STEPS:
                jax.profiler.stop_trace()


def _read_xplane(trace_dir):
    """(profile_start_time ns, host events {name: [(start, duration)]},
    starts of device 0's program runs), all in the file's nanoseconds."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    check(len(paths) == 1, "trace_clock: %d xplane files under %s"
          % (len(paths), trace_dir))
    data = jax.profiler.ProfileData.from_file(paths[0])
    origin, host, modules = None, {}, []
    for plane in data.planes:
        origin = dict(plane.stats).get("profile_start_time", origin)
        for line in plane.lines:
            if plane.name == "/host:CPU":
                for ev in line.events:
                    host.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
            elif plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                modules += [ev.start_ns for ev in line.events]
    check(origin is not None, "trace_clock: the xplane states no "
          "profile_start_time")
    return int(origin), host, sorted(modules)


def trace_clock_phase(start_t0):
    dev_id = 1 if TINY else 0
    trace_dir = tempfile.mkdtemp(prefix="chip_smoke_trace_")
    tracing.reset()
    try:
        watch, _real, _disk, _fused = run_fit(str(dev_id), 32,
                                              TraceWatch(trace_dir))
        origin, host, modules = _read_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    since_start = time.perf_counter() - start_t0
    check(len(watch.stamps) == TRACED_STEPS and
          len(host.get(PROBE, ())) == TRACED_STEPS,
          "trace_clock: %d probes stamped, %d in the trace"
          % (len(watch.stamps), len(host.get(PROBE, ()))))
    # which Python clock the file's host events are on, counted from its
    # profile_start_time: the one all probes agree with to 0.2 ms
    off_ms = {}
    for k, (name, _clock) in enumerate(CLOCKS):
        off_ms[name] = max(abs(origin + start - stamp[k]) * 1e-6
                           for (start, _dur), stamp
                           in zip(sorted(host[PROBE]), watch.stamps))
    on = [name for name, ms in off_ms.items() if ms < 0.2]
    print("[chip_smoke] trace_clock: xplane host events are on %s - "
          "profile_start_time (largest |offset| over %d probes, ms: %s)"
          % (on or "NO Python clock", TRACED_STEPS,
             json.dumps(off_ms, sort_keys=True)), flush=True)
    check(on == ["time.time_ns"],
          "trace_clock: tracing.py's docs say the xplane is on "
          "time.time_ns, it is on %s" % (on or off_ms))
    # every training span is an event of the trace under its own name,
    # and the log's perf_counter stamps are that event's start and end:
    # the first probe's pair of stamps carries one clock to the other
    # (the file's nanoseconds + to_log = perf_counter nanoseconds)
    to_log = origin - (watch.stamps[0][0] - watch.stamps[0][2])
    log = tracing.span_log()
    worst = {}
    for name in TRAIN_SPANS:
        events = sorted(host.get(name, ()))
        check(len(events) >= TRACED_STEPS - 1,
              "trace_clock: %d %s events on the trace's host plane, "
              "expected one a step" % (len(events), name))
        spans = [(r["t0"] * 1e9, r["t1"] * 1e9) for r in log
                 if r["name"] == name]
        for start, dur in events:
            t0, t1 = min(spans, key=lambda s: abs(s[0] - to_log - start))
            worst[name] = max(worst.get(name, 0.0),
                              abs(t0 - to_log - start) * 1e-6,
                              abs(t1 - to_log - start - dur) * 1e-6)
    check(max(worst.values()) < 0.2,
          "trace_clock: a logged span and its own annotation differ by "
          "more than 0.2 ms: %s" % json.dumps(worst, sort_keys=True))
    # information: how long after the fused program's call began the
    # device started on it (the first program run on device 0 after it)
    lead_ms = None
    dispatches = sorted(s for s, _d in host.get("executor.train_step", ()))
    after = [min(m for m in modules if m >= d) - d
             for d in dispatches if any(m >= d for m in modules)]
    if after:
        lead_ms = min(after) * 1e-6
    status("trace_clock", "passed", xplane_clock="time.time_ns (CLOCK_REALTIME)"
           " - profile_start_time", probe_offset_ms=round(
               off_ms["time.time_ns"], 4),
           worst_span_offset_ms=round(max(worst.values()), 4),
           worst_span=max(worst, key=worst.get), spans_checked=sorted(worst),
           seconds_since_start=round(since_start, 1),
           device_start_after_dispatch_start_ms=None
           if lead_ms is None else round(lead_ms, 3))


# ---------------------------------------------------------------------------
# phase dp4
# ---------------------------------------------------------------------------

def _reference_first_loss(batch):
    """First-step loss of the same seed and the same ``batch``-row
    synthetic batch on ONE chip: bind + init exactly as fit does, one
    training-mode forward (batch-norm uses batch statistics)."""
    ns = argparse.Namespace(**_model(batch))
    net = train_imagenet.get_network(ns)
    image = tuple(int(x) for x in ns.image_shape.split(","))
    from common import data as exdata
    it = exdata.SyntheticDataIter(ns.num_classes,
                                  (ns.batch_size,) + image, 1, "float32")
    mx.random.seed(0)
    mod = mx.module.Module(net, context=mx.tpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    batch0 = it.next()
    mod.forward(batch0, is_train=True)
    return _cross_entropy(mod, batch0)


def dp4_phase(devs):
    if len(devs) < 4:
        why = "%d device(s) visible, the phase needs 4" % len(devs)
        status("dp4", "SKIPPED", reason=why)
        return why
    t0 = time.perf_counter()
    ref = _reference_first_loss(128)
    watch, real, disk, fused_added = run_fit("0,1,2,3", 128)
    wall = time.perf_counter() - t0
    _check_steps("dp4", watch, fused_added)
    mod = watch.module
    exe = mod._exec
    four = [mx.tpu(i).jax_device() for i in range(4)]
    data = exe.arg_dict["data"]._data
    shard_devs = [s.device for s in data.addressable_shards]
    check(sorted(d.id for d in shard_devs) == sorted(d.id for d in four),
          "dp4: batch shards sit on %s, expected one on each of %s"
          % (shard_devs, four))
    rows = set(s.data.shape[0] for s in data.addressable_shards)
    check(rows == {data.shape[0] // 4},
          "dp4: batch shards hold %s rows of %d" % (rows, data.shape[0]))
    for name in mod._param_names:
        arr = exe.arg_dict[name]._data
        check(arr.devices() == set(four) and arr.is_fully_replicated,
              "dp4: parameter %s is not replicated on the 4 chips: %s"
              % (name, arr.sharding))
    hbm = [_hbm_in_use(d) for d in four]
    check(all(h is None or h > 0 for h in hbm),
          "dp4: HBM in use per chip %s" % hbm)
    tol = 1e-3 * max(1.0, abs(ref))
    check(abs(watch.loss[0] - ref) <= tol,
          "dp4: first-step loss %.6f on 4 chips vs %.6f on one chip"
          % (watch.loss[0], ref))
    steady = sorted(watch.wall[WARMUP_STEPS:])
    status("dp4", "passed", devices=[str(d) for d in four],
           loss_first=round(watch.loss[0], 5),
           loss_first_one_chip=round(ref, 5),
           loss_last=round(watch.loss[-1], 4),
           hbm_in_use_mbytes=[None if h is None else round(h / 1e6, 1)
                              for h in hbm],
           first_step_wall_s=round(watch.wall[0], 2),
           steady_ms_per_step=round(steady[len(steady) // 2] * 1e3, 2),
           phase_wall_s=round(wall, 2), real_compiles=real, disk_hits=disk)
    return None


def main():
    t0 = time.perf_counter()
    devs = device_phase()
    verdicts = {}
    for phase, run in (("train", train_phase), ("kernels", kernels_phase),
                       ("trace_clock", lambda: trace_clock_phase(t0)),
                       ("dp4", lambda: dp4_phase(devs))):
        if phase not in ARGS.phases:
            verdicts[phase] = "NOT RUN (--phases)"
            continue
        skipped = run()
        verdicts[phase] = "SKIPPED (%s)" % skipped if skipped else "passed"
    d0 = devs[0]
    print("[chip_smoke] summary: %s; wall %.1fs%s"
          % (", ".join("%s %s" % kv for kv in verdicts.items()),
             time.perf_counter() - t0,
             "; REHEARSAL on the host CPU — nothing about the device was "
             "proven" if TINY else ""), flush=True)
    if set(ARGS.phases) != set(PHASES) or ARGS.cases:
        sys.exit("chip_smoke: partial run (--phases %s): no verdict"
                 % ",".join(ARGS.phases))
    result = {"ok": True, "device": {"platform": d0.platform,
                                     "kind": d0.device_kind,
                                     "count": len(devs)}}
    if TINY:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
