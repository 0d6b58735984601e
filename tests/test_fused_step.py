"""Fused train-step tests: one donated XLA program per step
(Executor.train_step) must be bitwise-identical to the unfused
forward-jit / vjp-jit / per-parameter-update sequence, cost exactly ONE
host dispatch, and never recompile on learning-rate changes.

Reference analogs: the GraphExecutor's op bulking + the fused optimizer
kernels of src/operator/optimizer_op.cc, collapsed across the step
boundary.
"""
import os
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io
from mxnet_tpu import telemetry as tm
from mxnet_tpu.module import Module


def _mlp_sym(hidden=(32, 16), num_classes=10):
    net = mx.sym.Variable("data")
    for i, h in enumerate(hidden):
        net = mx.sym.FullyConnected(net, name="fc%d" % (i + 1), num_hidden=h)
        net = mx.sym.Activation(net, name="relu%d" % (i + 1),
                                act_type="relu")
    net = mx.sym.FullyConnected(net, name="fcout", num_hidden=num_classes)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batches(steps, batch, dim=64, num_classes=10, seed=3):
    rng = np.random.RandomState(seed)
    return [io.DataBatch(
        data=[mx.nd.array(rng.randn(batch, dim).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, num_classes, batch)
                           .astype(np.float32))])
        for _ in range(steps)]


def _make_module(optimizer, opt_params, batch=16, dim=64, seed=11,
                 lr_scheduler=None):
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, dim))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    rng = np.random.RandomState(seed)
    args = {n: mx.nd.array(rng.randn(*a.shape).astype(np.float32) * 0.1)
            for n, a in mod._exec.arg_dict.items()
            if n not in ("data", "softmax_label")}
    mod.set_params(args, {}, allow_missing=True, force_init=True)
    params = dict(opt_params)
    if lr_scheduler is not None:
        params["lr_scheduler"] = lr_scheduler
    mod.init_optimizer(optimizer=optimizer, optimizer_params=params)
    return mod


def _train(mod, batches):
    for db in batches:
        mod.forward_backward(db)
        mod.update()
    return {n: mod._exec.arg_dict[n].asnumpy() for n in mod._param_names}


def _step_leaves(record, fn, shardings=None):
    """Wrap jitted ``fn`` so each call appends the leaves of its arguments
    to ``record`` (what the program is actually handed); the first call
    also fills ``shardings`` with where the compiled program holds each
    leaf."""
    import jax

    def wrapped(*args):
        record.append(jax.tree_util.tree_leaves(args))
        if shardings is not None and not shardings:
            shardings.extend(jax.tree_util.tree_leaves(
                fn.lower(*args).compile().input_shardings[0]))
        return fn(*args)
    return wrapped


def _record_fused_programs(exe, record, shardings=None):
    """Put :func:`_step_leaves` round every fused-step program ``exe`` holds."""
    for key, fn in list(exe._fused_jitted.items()):
        exe._fused_jitted[key] = _step_leaves(record, fn, shardings)


def _assert_one_hyper_array(leaves, n_params):
    import jax
    scalars = [x for x in leaves if isinstance(x, (bool, int, float))]
    assert not scalars, "python scalars among the program's leaves"
    host = [x for x in leaves if not isinstance(x, jax.Array)]
    assert len(host) == 1, [type(x) for x in host]
    assert isinstance(host[0], np.ndarray)
    assert host[0].dtype == np.float32 and host[0].shape[0] == n_params
    return host[0]


OPT_CONFIGS = [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4,
             "clip_gradient": 0.5, "rescale_grad": 1.0 / 16}),
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4}),
    ("adam", {"learning_rate": 1e-3, "clip_gradient": 1.0,
              "rescale_grad": 1.0 / 16}),
]


@pytest.mark.parametrize("optimizer,opt_params", OPT_CONFIGS)
def test_fused_unfused_bitwise_parity(monkeypatch, optimizer, opt_params):
    """N fused steps == N unfused steps, bit for bit (SGD momentum/wd,
    Adam, clip_gradient/rescale_grad)."""
    batches = _batches(5, 16)

    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod_f = _make_module(optimizer, opt_params)
    assert mod_f._fused_step_ok()
    fused = _train(mod_f, batches)
    # the fused path must actually have run (one cached program, N steps)
    assert mod_f._exec._fused_jitted, "fused program cache is empty"

    monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    mod_u = _make_module(optimizer, opt_params)
    assert not mod_u._fused_step_ok()
    unfused = _train(mod_u, batches)

    assert set(fused) == set(unfused)
    for name in fused:
        assert np.array_equal(fused[name], unfused[name]), \
            "param %r diverged (max |d|=%g)" % (
                name, np.max(np.abs(fused[name] - unfused[name])))


@pytest.mark.parametrize("optimizer,opt_params", [
    # one representative per rule family in tier-1; the rest ride the
    # slow marker (full coverage, outside the tier-1 time budget)
    ("signum", {"learning_rate": 0.01, "momentum": 0.9, "wd_lh": 1e-4}),
    ("rmsprop", {"learning_rate": 1e-3, "centered": True}),
    ("adagrad", {"learning_rate": 0.05}),
    # eager update() clips whenever clip_gradient is set, even 0.0 —
    # the fused hyper must reproduce that (not the kernels' >0 gate)
    ("adagrad", {"learning_rate": 0.05, "clip_gradient": 0.0}),
    ("ftrl", {}),
    pytest.param("nag", {"learning_rate": 0.05, "momentum": 0.9},
                 marks=pytest.mark.slow),
    pytest.param("adadelta", {}, marks=pytest.mark.slow),
    pytest.param("ftml", {}, marks=pytest.mark.slow),
    pytest.param("adamax", {}, marks=pytest.mark.slow),
])
def test_fused_unfused_parity_other_optimizers(monkeypatch, optimizer,
                                               opt_params):
    """The remaining fused rules track their unfused kernels. Gradients
    and optimizer states stay bitwise-identical; the weights themselves
    may differ in the last ulp because XLA fuses the update arithmetic
    with the gradient producer (FMA contraction) where the unfused path
    rounds between separately-compiled kernels — so weights get a
    one-ulp-tight allclose here (the strict bitwise guarantee is
    asserted above for SGD/Adam, whose update kernels fuse identically)."""
    batches = _batches(4, 16)
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    fused = _train(_make_module(optimizer, opt_params), batches)
    monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    unfused = _train(_make_module(optimizer, opt_params), batches)
    for name in fused:
        np.testing.assert_allclose(fused[name], unfused[name],
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_fused_step_single_dispatch(monkeypatch):
    """One fused step = exactly ONE op dispatch (the fused_train_step
    program launch); the per-op eager counters must not tick for ops now
    executing inside the fused program (the double-count fix)."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    prev = tm.enable(True)
    try:
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9})
        batches = _batches(3, 16)
        _train(mod, batches[:2])            # build + warm the program

        before = tm.snapshot()
        fam = tm.REGISTRY._families.get("op/dispatch_total")
        per_op_before = {lv: c.value for lv, c in fam.series()}
        mod.forward_backward(batches[2])
        mod.update()
        after = tm.snapshot()

        assert after["op_dispatch_total"] - before["op_dispatch_total"] == 1
        assert after["fused_step_total"] - before["fused_step_total"] == 1
        per_op_after = {lv: c.value for lv, c in fam.series()}
        for lv, count in per_op_after.items():
            if lv == ("fused_train_step",):
                assert count == per_op_before.get(lv, 0) + 1
            else:
                assert count == per_op_before.get(lv, 0), \
                    "per-op counter %r ticked during a fused step" % (lv,)
    finally:
        tm.enable(prev)


def test_lr_schedule_does_not_recompile(monkeypatch):
    """10 steps under a per-step decaying LR schedule: zero XLA backend
    compiles (jax.monitoring listener) and zero fused program rebuilds —
    the lr is a traced scalar, not a baked constant."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    prev = tm.enable(True)      # installs the jax.monitoring listener
    try:
        sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.9)
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                           lr_scheduler=sched)
        batches = _batches(12, 16)
        _train(mod, batches[:2])            # compile + commit buffers

        record = []
        exe = mod._exec
        _record_fused_programs(exe, record)
        lr_before = mod._optimizer._get_lr(0)
        compiles_before = tm.compile_count()
        builds_before = tm.snapshot()["fused_step_compiles"]
        _train(mod, batches[2:])
        assert tm.compile_count() == compiles_before, \
            "lr schedule step retriggered XLA compilation"
        assert tm.snapshot()["fused_step_compiles"] == builds_before
        # the schedule travels in the ONE packed array: its lr column
        # (sorted keys: lr, momentum, rescale_grad, wd) falls every
        # step, its other columns stand, and no program was added
        assert len(exe._fused_jitted) == 1 and len(record) == 10
        hypers = [_assert_one_hyper_array(leaves, len(mod._param_names))
                  for leaves in record]
        lrs = [float(h[0, 0]) for h in hypers]
        assert all(b < a for a, b in zip(lrs, lrs[1:])), lrs
        for h in hypers:
            np.testing.assert_array_equal(h[:, 1:], hypers[0][:, 1:])
        # the schedule really advanced (so the zero-recompile claim is
        # about changing lr values, not a frozen schedule)
        assert mod._optimizer._get_lr(0) < lr_before * 0.5
    finally:
        tm.enable(prev)


def test_fused_convergence_and_states(monkeypatch):
    """Fused fit converges like the unfused path and keeps the Updater's
    state dict live for save/load_optimizer_states."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    rng = np.random.RandomState(7)
    centers = rng.randn(10, 64).astype(np.float32) * 1.5
    labels = rng.randint(0, 10, size=500)
    data = (centers[labels] + rng.randn(500, 64)).astype(np.float32)
    it = io.NDArrayIter(data, labels.astype(np.float32), batch_size=50,
                        shuffle=True)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=5, optimizer="sgd",
            initializer=mx.init.Xavier(magnitude=2.0),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    score = mod.score(io.NDArrayIter(data, labels.astype(np.float32),
                                     batch_size=50), "acc")
    assert score[0][1] > 0.95, score
    # momentum states materialized in the Updater (index-keyed, NDArray)
    states = mod._updater.states
    assert states and all(s is not None for s in states.values())
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".states") as f:
        mod.save_optimizer_states(f.name)
        mod.load_optimizer_states(f.name)


def test_fused_fallbacks(monkeypatch):
    """Monitors, non-write grad_req, multi-precision, unknown-rule
    optimizers, and MXNET_FUSED_STEP=0 all disable the fused step."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod = _make_module("sgd", {"learning_rate": 0.1})
    assert mod._fused_step_ok()

    monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    assert not mod._fused_step_ok()
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")

    # a monitor needs per-op outputs -> unfused
    mod._exec.set_monitor_callback(lambda name, arr: None)
    assert not mod._fused_step_ok()
    mod._exec._monitor_callback = None
    assert mod._fused_step_ok()

    # optimizer without a pure rule -> unfused
    mod2 = _make_module("nadam", {"learning_rate": 1e-3})
    assert not mod2._fused_step_ok()
    batches = _batches(2, 16)
    _train(mod2, batches)                   # still trains via fallback
    assert not mod2._exec._fused_jitted

    # grad_req='add' -> unfused
    mod3 = Module(_mlp_sym(), context=mx.cpu())
    mod3.bind(data_shapes=[("data", (16, 64))],
              label_shapes=[("softmax_label", (16,))], grad_req="add")
    mod3.init_params()
    mod3.init_optimizer(optimizer="sgd")
    assert not mod3._fused_step_ok()

    # multi-precision -> unfused
    mod4 = _make_module("sgd", {"learning_rate": 0.1,
                                "multi_precision": True})
    assert not mod4._fused_step_ok()


def test_get_outputs_mid_step_replays_unfused(monkeypatch):
    """Inspecting outputs between forward_backward() and update() keeps
    exact legacy semantics: the deferred batch is replayed unfused, so
    the user sees THIS batch's outputs and the whole run matches a pure
    unfused run bitwise."""
    batches = _batches(3, 16, seed=8)

    def run(fused, peek):
        monkeypatch.setenv("MXNET_FUSED_STEP", "1" if fused else "0")
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9})
        peeked = []
        for db in batches:
            mod.forward_backward(db)
            if peek:
                peeked.append(mod.get_outputs()[0].asnumpy())
            mod.update()
        params = {n: mod._exec.arg_dict[n].asnumpy()
                  for n in mod._param_names}
        return params, peeked

    fused_params, fused_outs = run(True, peek=True)
    ref_params, ref_outs = run(False, peek=True)
    for a, b in zip(fused_outs, ref_outs):
        assert np.array_equal(a, b)
    for name in ref_params:
        assert np.array_equal(fused_params[name], ref_params[name]), name


def test_deferred_batch_cleared_on_unfused_fallback(monkeypatch):
    """A batch deferred by the fused path must not be replayed by a later
    update() after the configuration flipped to unfused mid-step — the
    run must match a pure unfused run on the same batch sequence."""
    b1, b2 = _batches(2, 16, seed=9)

    def run(flip):
        monkeypatch.setenv("MXNET_FUSED_STEP", "1" if flip else "0")
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9})
        if flip:
            mod.forward_backward(b1)        # deferred (fused eligible)
            monkeypatch.setenv("MXNET_FUSED_STEP", "0")
        mod.forward_backward(b1)            # unfused fwd/bwd on b1
        mod.update()
        mod.forward_backward(b2)
        mod.update()
        return {n: mod._exec.arg_dict[n].asnumpy()
                for n in mod._param_names}

    flipped, reference = run(True), run(False)
    for name in reference:
        assert np.array_equal(flipped[name], reference[name]), \
            "stale deferred batch leaked into the unfused step (%s)" % name


def test_forward_kwargs_device_placement():
    """Host inputs fed through forward(**kwargs) must land on the
    executor's bound context, not JAX's default device."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    ctx = mx.cpu(1)
    sym = _mlp_sym()
    exe = sym.simple_bind(
        ctx, grad_req={n: "null" for n in sym.list_arguments()},
        data=(8, 64), softmax_label=(8,))
    exe.forward(is_train=False, data=np.zeros((8, 64), np.float32))
    placed = exe.arg_dict["data"]._data
    assert list(placed.devices()) == [ctx.jax_device()]
    assert exe.outputs[0].shape == (8, 10)


def test_backward_add_accumulates_inside_program():
    """grad_req='add' accumulation runs inside the jitted vjp: two
    backward passes double the gradient, with no per-parameter host-side
    add."""
    sym = _mlp_sym()
    reqs = {n: "null" if n in ("data", "softmax_label") else "add"
            for n in sym.list_arguments()}
    exe = sym.simple_bind(mx.cpu(0), grad_req=reqs, data=(8, 64),
                          softmax_label=(8,))
    rng = np.random.RandomState(0)
    for n, arr in exe.arg_dict.items():
        if n not in ("data", "softmax_label"):
            arr._set_data(mx.nd.array(
                rng.randn(*arr.shape).astype(np.float32) * 0.1)._data)
    feed = {"data": rng.randn(8, 64).astype(np.float32),
            "softmax_label": rng.randint(0, 10, 8).astype(np.float32)}
    exe.forward(is_train=True, **feed)
    exe.backward()
    g1 = exe.grad_dict["fc1_weight"].asnumpy().copy()
    assert np.abs(g1).sum() > 0
    exe.forward(is_train=True, **feed)
    exe.backward()
    g2 = exe.grad_dict["fc1_weight"].asnumpy()
    np.testing.assert_allclose(g2, 2 * g1, rtol=1e-6, atol=1e-7)


def test_trainer_fused_apply(monkeypatch):
    """Gluon Trainer.step: the whole-pytree fused update matches the
    per-parameter path and costs one dispatch."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        return net

    def run(fused, steps=4):
        monkeypatch.setenv("MXNET_FUSED_STEP", "1" if fused else "0")
        rng = np.random.RandomState(2)
        net = build()
        net(mx.nd.zeros((8, 8)))        # materialize deferred shapes
        seed_rng = np.random.RandomState(5)
        for _name, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                seed_rng.randn(*p.shape).astype(np.float32) * 0.1))
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        x = mx.nd.array(rng.randn(8, 8).astype(np.float32))
        y = mx.nd.array(rng.randn(8, 4).astype(np.float32))
        lfn = gluon.loss.L2Loss()
        for _ in range(steps):
            with autograd.record():
                loss = lfn(net(x), y)
            loss.backward()
            trainer.step(8)
        # block name counters are process-global, so key by the suffix
        # (dense0_weight, ...) which is stable across the two runs
        return {name.split("_", 1)[1]: p.data().asnumpy()
                for name, p in net.collect_params().items()}

    fused = run(True)
    unfused = run(False)
    assert set(fused) == set(unfused) and len(fused) == 4
    for name in fused:
        assert np.array_equal(fused[name], unfused[name]), name


def test_trainer_fused_single_dispatch(monkeypatch):
    """After warmup, a Trainer step's update is ONE dispatch
    (fused_optimizer_update), not one per parameter."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    prev = tm.enable(True)
    try:
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        rng = np.random.RandomState(2)
        x = mx.nd.array(rng.randn(8, 8).astype(np.float32))
        y = mx.nd.array(rng.randn(8, 4).astype(np.float32))
        lfn = gluon.loss.L2Loss()

        def step():
            with autograd.record():
                loss = lfn(net(x), y)
            loss.backward()
            trainer.step(8)

        step()                                  # warm
        fam = tm.REGISTRY._families.get("op/dispatch_total")
        before = {lv: c.value for lv, c in fam.series()}
        step()
        after = {lv: c.value for lv, c in fam.series()}
        assert (after.get(("fused_optimizer_update",), 0)
                - before.get(("fused_optimizer_update",), 0)) == 1
        for name in ("sgd_mom_update", "sgd_update"):
            assert after.get((name,), 0) == before.get((name,), 0), \
                "per-param optimizer kernel dispatched on the fused path"
    finally:
        tm.enable(prev)


def test_fused_step_dp_mesh_matches_single_device(monkeypatch):
    """The fused program under a data-parallel mesh (GSPMD folds the
    gradient all-reduce into the same program) tracks single-device
    training."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")

    handed = {}

    def losses(contexts, steps=6, batch=32):
        rng = np.random.RandomState(4)
        centers = rng.randn(10, 64).astype(np.float32) * 1.5
        labels = rng.randint(0, 10, size=256)
        data = (centers[labels] + rng.randn(256, 64)).astype(np.float32)
        mod = Module(_mlp_sym(), context=contexts)
        mod.bind(data_shapes=[("data", (batch, 64))],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params()
        prng = np.random.RandomState(11)
        args = {n: mx.nd.array(prng.randn(*a.shape).astype(np.float32)
                               * 0.05)
                for n, a in mod._exec.arg_dict.items()
                if n not in ("data", "softmax_label")}
        mod.set_params(args, {}, allow_missing=True, force_init=True)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        out = []
        record = handed[len(mod._context)] = []
        for i in range(steps):
            lo = (i * batch) % (len(data) - batch)
            db = io.DataBatch(
                data=[mx.nd.array(data[lo:lo + batch])],
                label=[mx.nd.array(labels[lo:lo + batch])])
            mod.forward_backward(db)
            mod.update()
            if i == 0:
                shardings = handed["shardings", len(mod._context)] = []
                _record_fused_programs(mod._exec, record, shardings)
            probs = mod.get_outputs()[0].asnumpy()
            li = labels[lo:lo + batch].astype(int)
            out.append(float(-np.mean(np.log(np.maximum(
                probs[np.arange(batch), li], 1e-10)))))
        assert mod._exec._fused_jitted, "fused path did not engage"
        return out

    single = losses(mx.cpu(0))
    multi = losses([mx.cpu(i) for i in range(4)])
    np.testing.assert_allclose(multi, single, rtol=2e-4, atol=2e-5)
    assert single[-1] < single[0], "training did not reduce loss"
    # under the mesh the step is handed the same ONE host array as on one
    # device, and the program holds it replicated on every device
    assert len(handed[1]) == len(handed[4]) == 5
    for one, four in zip(handed[1], handed[4]):
        np.testing.assert_array_equal(_assert_one_hyper_array(one, 6),
                                      _assert_one_hyper_array(four, 6))
    import jax
    (hyper_sharding,) = [
        sh for sh, x in zip(handed["shardings", 4], handed[4][0])
        if not isinstance(x, jax.Array)]
    assert hyper_sharding.is_fully_replicated
    assert len(hyper_sharding.device_set) == 4


# ---------------------------------------------------------------------------
# the packed hyper-parameter array (optimizer.pack_fused_hyper): the scalars
# of every updated parameter cross to the device as ONE float32 array a step
# ---------------------------------------------------------------------------

FUSED_RULE_OPTIMIZERS = [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.1}),                       # stateless
    ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
    ("signum", {"learning_rate": 0.01, "momentum": 0.9, "wd_lh": 1e-4}),
    ("adam", {"learning_rate": 1e-3, "wd": 1e-4, "clip_gradient": 1.0}),
    ("adagrad", {"learning_rate": 0.05}),
    ("rmsprop", {"learning_rate": 1e-3}),
    ("rmsprop", {"learning_rate": 1e-3, "centered": True,
                 "clip_weights": 0.5}),
    ("adadelta", {}),
    ("ftrl", {}),
    ("ftml", {}),
    ("adamax", {}),
    ("test", {"learning_rate": 0.1}),
]


def test_every_fused_rule_optimizer_is_covered():
    """The list above names every optimizer that has a ``fused_rule``."""
    have = {name for name, klass in mx.optimizer.Optimizer.opt_registry.items()
            if klass.fused_rule is not mx.optimizer.Optimizer.fused_rule}
    assert have == {name for name, _ in FUSED_RULE_OPTIMIZERS}


@pytest.mark.parametrize("optimizer,opt_params", FUSED_RULE_OPTIMIZERS)
def test_packed_step_bitwise_matches_scalar_rule(optimizer, opt_params):
    """Three steps of ``fused_apply`` (the packed array, unpacked in the
    trace) leave weights and states bitwise where the same rule, jitted
    over the dicts of python scalars as it was before the array, leaves
    them — fp32, every optimizer with a ``fused_rule``."""
    import jax
    from mxnet_tpu import optimizer as opt
    rng = np.random.RandomState(5)
    shapes = [(8, 6), (6,), (3, 4, 2)]
    w0 = [rng.randn(*s).astype(np.float32) * 0.3 for s in shapes]
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(3)]

    def make():
        o = opt.create(optimizer, **opt_params)
        ws = [mx.nd.array(w) for w in w0]
        states = [o.create_state(i, w) for i, w in enumerate(ws)]
        return o, ws, states

    o, ws, states = make()
    for step in grads:
        items = [(i, ws[i], mx.nd.array(step[i]), states[i])
                 for i in range(len(ws))]
        assert opt.fused_apply(o, items)
    got_w = [w.asnumpy() for w in ws]
    got_s = [[a.asnumpy() for a in opt.fused_state_arrays(s)]
             for s in states]

    o, ws, states = make()
    rule = o.fused_rule()
    scalar_step = jax.jit(lambda w, g, s, hs: [
        rule(w[i], g[i], s[i], hs[i]) for i in range(len(w))])
    w = [a._data for a in ws]
    s = [tuple(a._data for a in opt.fused_state_arrays(st))
         for st in states]
    for step in grads:
        hs = [o.fused_hyper(i) for i in range(len(w))]
        assert all(isinstance(v, float) for h in hs for v in h.values())
        new = scalar_step(w, [mx.nd.array(g)._data for g in step], s, hs)
        w, s = [n[0] for n in new], [n[1] for n in new]

    for i in range(len(shapes)):
        assert np.array_equal(got_w[i], np.asarray(w[i])), (optimizer, i)
        assert len(got_s[i]) == len(s[i])
        for a, b in zip(got_s[i], s[i]):
            assert np.array_equal(a, np.asarray(b)), (optimizer, i)


# ---------------------------------------------------------------------------
# the update runs in each parameter's own layout: a rule is elementwise over
# operands of ONE shape (XLA's fusion reads and writes them where they lie),
# and the step program wraps it in nothing
# ---------------------------------------------------------------------------

# what the shape check below cannot see (a kernel, a reversal or a square
# transpose keeps the shape) and, for the message, what it can
_RELAYOUT_PRIMITIVES = {
    "reshape", "pad", "slice", "dynamic_slice", "dynamic_update_slice",
    "concatenate", "transpose", "squeeze", "expand_dims", "gather",
    "scatter", "rev", "pallas_call", "custom_call", "shard_map"}


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (``jnp.clip`` and ``jnp.where`` trace as nested ``pjit`` calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _walk_eqns(inner)


@pytest.mark.parametrize("shape", [(64, 3, 7, 7), (7,)])
@pytest.mark.parametrize("optimizer,opt_params", FUSED_RULE_OPTIMIZERS)
def test_fused_rule_is_elementwise_in_the_operands_layout(optimizer,
                                                          opt_params, shape):
    """``jax.make_jaxpr`` of every ``fused_rule``: nothing flattens, pads,
    reshapes, slices or transposes a weight, a gradient or a state, and
    nothing calls a kernel — each value is the parameter's shape or a
    scalar — and weight and states come back in the operand's shape and
    dtype. On the TPU a reshape of a (512, 512, 3, 3) weight is a physical
    relayout (157 parameters' worth cost 53 ms a step until PR 33)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt
    o = opt.create(optimizer, **opt_params)
    w = mx.nd.zeros(shape)
    state = tuple(a._data for a in opt.fused_state_arrays(
        o.create_state(0, w)))
    hyper = {k: jnp.asarray(v, jnp.float32)
             for k, v in o.fused_hyper(0).items()}
    rule = o.fused_rule()
    closed = jax.make_jaxpr(rule)(w._data, w._data, state, hyper)
    seen = set()
    for eqn in _walk_eqns(closed.jaxpr):
        seen.add(eqn.primitive.name)
        for var in eqn.outvars:
            assert var.aval.shape in (shape, ()), (eqn.primitive.name,
                                                   var.aval.shape)
    assert not seen & _RELAYOUT_PRIMITIVES, seen & _RELAYOUT_PRIMITIVES
    new_w, new_s = jax.eval_shape(rule, w._data, w._data, state, hyper)
    assert (new_w.shape, new_w.dtype) == (shape, w._data.dtype)
    assert len(new_s) == len(state)
    for a in new_s:
        assert (a.shape, a.dtype) == (shape, w._data.dtype)


def _conv_bn_fc_sym():
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, name="conv", num_filter=8, kernel=(3, 3),
                             pad=(1, 1), no_bias=True)
    net = mx.sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = mx.sym.Activation(net, name="relu", act_type="relu")
    net = mx.sym.Pooling(net, name="pool", global_pool=True, kernel=(1, 1),
                         pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), name="fc", num_hidden=5)
    return mx.sym.SoftmaxOutput(net, name="softmax")


@pytest.mark.parametrize("devices", [1, 2])
def test_step_program_wraps_the_update_in_nothing(monkeypatch, devices):
    """The lowered text of ``Executor.train_step`` for a conv-BN-FC symbol,
    alone and under a two-device dp mesh: no custom call but GSPMD's own
    sharding annotations (so no kernel in the update tail for a reshape to
    feed), and no ``shard_map`` / manual-sharding region — every operand of
    the rule is replicated, so GSPMD runs it on each replica as it is."""
    import re
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    contexts = mx.cpu(0) if devices == 1 else \
        [mx.cpu(i) for i in range(devices)]
    mod = Module(_conv_bn_fc_sym(), context=contexts)
    mod.bind(data_shapes=[("data", (8, 3, 8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    rng = np.random.RandomState(0)
    db = io.DataBatch(
        data=[mx.nd.array(rng.randn(8, 3, 8, 8).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 5, 8).astype(np.float32))])
    mod.forward_backward(db)
    mod.update()
    exe = mod._exec
    ((key, fn),) = exe._fused_jitted.items()
    texts = []

    def lowered_then_run(*args):
        texts.append(fn.lower(*args).as_text())       # before the donation
        return fn(*args)
    exe._fused_jitted[key] = lowered_then_run
    mod.forward_backward(db)
    mod.update()
    (text,) = texts
    assert "stablehlo.convolution" in text          # the step, not a stub
    targets = set(re.findall(r"custom_call\s*@(\w+)", text))
    assert targets <= {"Sharding"}, targets
    for manual in ("shard_map", "manual_computation", "SPMDFullToShardShape",
                   "SPMDShardToFullShape", "manual_axes"):
        assert manual not in text, manual
    if devices > 1:
        assert "sharding" in text                   # the mesh is there


def test_train_step_hands_over_one_hyper_array(monkeypatch):
    """The leaves ``Executor.train_step`` hands its jitted program hold no
    python scalar and exactly one host array — the packed hypers, a row
    per updated parameter — and the hand-over counter rises by 1 a step."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    prev = tm.enable(True)
    try:
        mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                                   "wd": 1e-4})
        batches = _batches(4, 16)
        _train(mod, batches[:1])                # build the program
        exe = mod._exec
        record = []
        _record_fused_programs(exe, record)

        def puts():
            fam = tm.REGISTRY._families.get(
                "executor/fused_step_hyper_put_total")
            return sum(c.value for _lv, c in fam.series())

        before = puts()
        _train(mod, batches[1:])
        assert puts() - before == 3
        assert len(record) == 3
        n_params = len(mod._param_names)
        for leaves in record:
            harr = _assert_one_hyper_array(leaves, n_params)
            # columns = sorted keys of SGD-momentum's fused_hyper:
            # lr, momentum, rescale_grad (1 / batch), wd
            assert harr.shape == (n_params, 4)
            np.testing.assert_array_equal(
                harr[0], np.float32([0.1, 0.9, 1.0 / 16, 1e-4]))
    finally:
        tm.enable(prev)


def test_fused_apply_hands_over_one_hyper_array():
    """Same for ``optimizer.fused_apply`` (the Gluon Trainer's update)."""
    from mxnet_tpu import optimizer as opt
    o = opt.create("adam", learning_rate=1e-3)
    ws = [mx.nd.ones((4, 3)), mx.nd.ones((3,))]
    states = [o.create_state(i, w) for i, w in enumerate(ws)]

    def items():
        return [(i, ws[i], mx.nd.ones(ws[i].shape), states[i])
                for i in range(2)]

    assert opt.fused_apply(o, items())
    record = []
    cache = o._fused_apply_cache
    for key, fn in list(cache.items()):
        cache[key] = _step_leaves(record, fn)
    assert opt.fused_apply(o, items())
    assert len(cache) == 1 and len(record) == 1
    harr = _assert_one_hyper_array(record[0], 2)
    assert harr.shape == (2, len(o.fused_hyper(0)))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_packed_hyper_keeps_narrow_dtype_and_donation(dtype):
    """A strong float32 scalar would promote a bfloat16/float16 update to
    float32; the unpacked scalars take the weight's dtype (where the weak
    python scalar was demoted), so outputs keep it, the donated buffers
    are reused, and the result is the python-scalar rule's bitwise."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt
    rng = np.random.RandomState(9)
    w0 = rng.randn(16, 8).astype(np.float32)
    g0 = rng.randn(16, 8).astype(np.float32)
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9, wd=1e-3)
    hyper = o.fused_hyper(0)
    keys, harr = opt.pack_fused_hyper([hyper])
    rule = o.fused_rule()

    def packed(w, g, s, hs):
        return rule(w, g, s, opt.unpack_fused_hyper(hs[0], keys, w.dtype))

    w = jnp.asarray(w0, dtype)
    g = jnp.asarray(g0, dtype)
    mom = jnp.zeros_like(w)
    want_w, (want_m,) = jax.jit(rule)(w, g, (mom,), hyper)
    assert want_w.dtype == jnp.dtype(dtype)

    lowered = jax.jit(packed, donate_argnums=(0, 2)).lower(
        w, g, (mom,), harr)
    assert lowered.as_text().count("tf.aliasing_output") == 2
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got_w, (got_m,) = jax.jit(packed, donate_argnums=(0, 2))(
            w, g, (mom,), harr)
    assert not [c for c in caught if "donated" in str(c.message)]
    assert got_w.dtype == jnp.dtype(dtype) and got_m.dtype == got_w.dtype
    assert w.is_deleted() and mom.is_deleted()
    assert np.array_equal(np.asarray(got_w, np.float32),
                          np.asarray(want_w, np.float32))
    assert np.array_equal(np.asarray(got_m, np.float32),
                          np.asarray(want_m, np.float32))


def test_fused_apply_narrow_dtype_end_to_end():
    """``fused_apply`` on a bfloat16 weight: dtype kept, in place."""
    from mxnet_tpu import optimizer as opt
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    w = mx.nd.ones((8, 4), dtype="bfloat16")
    s = o.create_state(0, w)
    assert opt.fused_apply(o, [(0, w, mx.nd.ones((8, 4), dtype="bfloat16"),
                                s)])
    assert str(w._data.dtype) == "bfloat16"
    assert str(s._data.dtype) == "bfloat16"
    np.testing.assert_allclose(w.asnumpy().astype(np.float32), 0.9,
                               rtol=1e-2)


def test_differing_hyper_key_sets_raise():
    """Nothing is padded: parameters whose hyper dicts differ in their
    keys are refused, by the helper and by ``train_step``."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="keys differ"):
        opt.pack_fused_hyper([{"lr": 0.1, "wd": 0.0},
                              {"lr": 0.1, "momentum": 0.9}])
    with pytest.raises(MXNetError, match="keys differ"):
        opt.pack_fused_hyper([{"lr": 0.1, "wd": 0.0}, {"lr": 0.1}])
    keys, arr = opt.pack_fused_hyper([{"b": 2.0, "a": 1.0}] * 3)
    assert keys == ("a", "b") and arr.shape == (3, 2)
    assert arr.dtype == np.float32 and arr[1].tolist() == [1.0, 2.0]
    assert opt.pack_fused_hyper([])[1].shape == (0, 0)

    mod = _make_module("sgd", {"learning_rate": 0.1, "momentum": 0.9})
    exe = mod._exec
    names = tuple(mod._param_names)
    states = {n: opt.fused_state_arrays(
        mod._updater.ensure_state(i, exe.arg_dict[n]))
        for i, n in enumerate(names)}
    hyper = {n: mod._optimizer.fused_hyper(i) for i, n in enumerate(names)}
    hyper[names[-1]]["clip_gradient"] = 1.0
    with pytest.raises(MXNetError, match="keys differ"):
        exe.train_step(mod._optimizer.fused_rule(), names, states, hyper)
