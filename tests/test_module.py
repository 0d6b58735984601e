"""Module training tests — the SURVEY §7 stage-4 judged milestone
(reference: tests/python/train/test_mlp.py, tests/python/unittest/test_module.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io
from mxnet_tpu.module import Module


def _synthetic_mnist(n=2000, seed=7):
    """MNIST-scale 10-class problem: 784-dim inputs whose class signal is a
    linear projection + nonlinearity, learnable to >97% by an MLP."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(10, 784).astype(np.float32) * 1.2
    labels = rng.randint(0, 10, size=n)
    data = centers[labels] + rng.randn(n, 784).astype(np.float32)
    return data.astype(np.float32), labels.astype(np.float32)


def _mlp_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3", num_hidden=10)
    return mx.sym.SoftmaxOutput(fc3, name="softmax")


def test_mlp_fit_convergence():
    """MNIST-equivalent convergence: >=97% train accuracy in a few epochs
    (mirrors tests/python/train/test_mlp.py accuracy assertion)."""
    data, labels = _synthetic_mnist()
    train = io.NDArrayIter(data, labels, batch_size=100, shuffle=True)
    val = io.NDArrayIter(data, labels, batch_size=100)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            num_epoch=5)
    score = mod.score(val, "acc")
    assert score[0][1] >= 0.97, "accuracy %f too low" % score[0][1]


def test_module_forward_shapes():
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 784))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params()
    batch = io.DataBatch(data=[mx.nd.zeros((16, 784))],
                         label=[mx.nd.zeros((16,))])
    mod.forward(batch, is_train=False)
    outs = mod.get_outputs()
    assert outs[0].shape == (16, 10)


def test_module_predict():
    data, labels = _synthetic_mnist(200)
    it = io.NDArrayIter(data, labels, batch_size=50)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    preds = mod.predict(it)
    assert preds.shape == (200, 10)


def test_module_checkpoint_roundtrip(tmp_path):
    data, labels = _synthetic_mnist(300)
    it = io.NDArrayIter(data, labels, batch_size=50)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.05})
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 1)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0001.params")

    mod2 = Module.load(prefix, 1, context=mx.cpu())
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    it.reset()
    p1 = mod.predict(it).asnumpy()
    it.reset()
    p2 = mod2.predict(it).asnumpy()
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_module_save_load_optimizer_states(tmp_path):
    data, labels = _synthetic_mnist(200)
    it = io.NDArrayIter(data, labels, batch_size=50)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    assert os.path.exists(prefix + "-0001.states")
    mod.load_optimizer_states(prefix + "-0001.states")


def test_module_adam_convergence():
    data, labels = _synthetic_mnist(1000)
    train = io.NDArrayIter(data, labels, batch_size=100, shuffle=True)
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="adam",
            optimizer_params={"learning_rate": 0.002}, num_epoch=4)
    score = mod.score(io.NDArrayIter(data, labels, batch_size=100), "acc")
    assert score[0][1] >= 0.95


def test_conv_module_trains():
    """Small LeNet-style conv net end to end (mirrors
    tests/python/train/test_conv.py)."""
    rng = np.random.RandomState(3)
    n = 400
    labels = rng.randint(0, 4, size=n)
    base = rng.randn(4, 1, 12, 12).astype(np.float32) * 2
    data = base[labels] + rng.randn(n, 1, 12, 12).astype(np.float32) * 0.5

    d = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(d, kernel=(3, 3), num_filter=8, name="conv1")
    a1 = mx.sym.Activation(c1, act_type="relu")
    p1 = mx.sym.Pooling(a1, kernel=(2, 2), stride=(2, 2), pool_type="max")
    fl = mx.sym.Flatten(p1)
    fc = mx.sym.FullyConnected(fl, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")

    it = io.NDArrayIter(data, labels.astype(np.float32), batch_size=40,
                        shuffle=True)
    mod = Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    score = mod.score(io.NDArrayIter(data, labels.astype(np.float32),
                                     batch_size=40), "acc")
    assert score[0][1] >= 0.95


def test_bucketing_module():
    """Variable-length input via BucketingModule (reference:
    tests/python/train/test_bucketing.py shape)."""
    buckets = [8, 16]

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        # params must be shape-invariant across buckets (as with shared
        # RNN weights in the reference): reduce the bucketed axis first
        pooled = mx.sym.mean(data, axis=1, keepdims=True)
        fc = mx.sym.FullyConnected(pooled, num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        return net, ("data",), ("softmax_label",)

    mod = mx.module.BucketingModule(sym_gen, default_bucket_key=16,
                                    context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 16))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(kvstore=None)
    for key in [16, 8, 16]:
        batch = io.DataBatch(
            data=[mx.nd.ones((4, key))], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[io.DataDesc("data", (4, key))],
            provide_label=[io.DataDesc("softmax_label", (4,))])
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    assert mod.get_outputs()[0].shape == (4, 4)


# ---------------------------------------------------------------------------
# SequentialModule + PythonModule (reference:
# python/mxnet/module/sequential_module.py:28, python_module.py:28)


def test_sequential_module_fit_convergence():
    """Two chained Modules (feature stack -> loss head) train through
    SequentialModule.fit to the same accuracy bar as the monolith."""
    from mxnet_tpu.module import SequentialModule

    data, labels = _synthetic_mnist(n=1000)
    train = io.NDArrayIter(data, labels, batch_size=100, shuffle=True)

    d = mx.sym.Variable("data")
    feat = mx.sym.Activation(
        mx.sym.FullyConnected(d, name="fc1", num_hidden=64),
        name="relu1", act_type="relu")
    d2 = mx.sym.Variable("data")
    head = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d2, name="fc2", num_hidden=10),
        name="softmax")

    seq = SequentialModule()
    seq.add(Module(feat, label_names=None, context=mx.cpu()))
    seq.add(Module(head, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    seq.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2}, num_epoch=4)
    metric = mx.metric.Accuracy()
    seq.score(io.NDArrayIter(data, labels, batch_size=100), metric)
    assert metric.get()[1] > 0.9, metric.get()


def test_sequential_module_shapes_and_params():
    from mxnet_tpu.module import SequentialModule

    d = mx.sym.Variable("data")
    feat = mx.sym.FullyConnected(d, name="fc1", num_hidden=8)
    head = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc2",
                              num_hidden=3), name="softmax")
    seq = SequentialModule()
    seq.add(Module(feat, label_names=None, context=mx.cpu()))
    seq.add(Module(head, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    seq.bind(data_shapes=[("data", (4, 5))],
             label_shapes=[("softmax_label", (4,))])
    seq.init_params()
    assert seq.data_names == ["data"]
    assert tuple(seq.output_shapes[0][1]) == (4, 3)
    args, _ = seq.get_params()
    assert set(args) == {"fc1_weight", "fc1_bias",
                         "fc2_weight", "fc2_bias"}


def test_python_loss_module_trains_in_chain():
    """A PythonLossModule (hand-written softmax-CE gradient) terminates
    the chain; the feature module still learns."""
    from mxnet_tpu.module import PythonLossModule, SequentialModule

    rng = np.random.RandomState(0)
    n, d, k = 400, 20, 4
    centers = rng.randn(k, d).astype(np.float32) * 2.0
    labels = rng.randint(0, k, size=n)
    data = centers[labels] + rng.randn(n, d).astype(np.float32) * 0.5
    it = io.NDArrayIter(data.astype(np.float32),
                        labels.astype(np.float32), batch_size=50,
                        shuffle=True)

    scores_sym = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                       name="fc", num_hidden=k)

    def softmax_ce_grad(scores, lab):
        s = scores.asnumpy()
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        onehot = np.eye(k, dtype=np.float32)[lab.asnumpy().astype(int)]
        return (p - onehot) / s.shape[0]

    seq = SequentialModule()
    seq.add(Module(scores_sym, label_names=None, context=mx.cpu()))
    seq.add(PythonLossModule(grad_func=softmax_ce_grad),
            take_labels=True, auto_wiring=True)
    seq.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    seq.init_params()
    seq.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})

    metric = mx.metric.Accuracy()
    for _ in range(6):
        it.reset()
        metric.reset()
        for batch in it:
            seq.forward(batch, is_train=True)
            seq.backward()
            seq.update()
            seq.update_metric(metric, batch.label)
    assert metric.get()[1] > 0.9, metric.get()


def test_bf16_end_to_end_convergence():
    """Mixed-precision end-to-end training at bfloat16 reaches the
    accuracy bar — the TPU analog of the reference's float16 training
    check (tests/python/train/test_dtype.py): bf16 params/compute,
    same convergence contract as fp32."""
    from mxnet_tpu import gluon, autograd

    data, labels = _synthetic_mnist(n=1000)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(64, activation="relu"))
        net.add(gluon.nn.Dense(10))
    net.initialize()
    net.cast("bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    it = io.NDArrayIter(data, labels, batch_size=100, shuffle=True)
    for _ in range(4):
        it.reset()
        for batch in it:
            x = batch.data[0].astype("bfloat16")
            y = batch.label[0]
            with autograd.record():
                out = loss_fn(net(x), y)
            out.backward()
            trainer.step(x.shape[0])

    correct = total = 0
    it.reset()
    for batch in it:
        pred = net(batch.data[0].astype("bfloat16")).asnumpy()
        pred = pred.astype(np.float32).argmax(axis=1)
        lab = batch.label[0].asnumpy()
        n_real = batch.data[0].shape[0] - batch.pad
        correct += (pred[:n_real] == lab[:n_real]).sum()
        total += n_real
    acc = correct / total
    assert acc > 0.9, acc


# ---------------------------------------------------------------------------
# Module.prepare pre-stages the next batch (Executor.prestage): fit against
# a manual loop over DISTINCT batches, bitwise. test_module_dp.py runs the
# same helpers over a 4-device dp mesh.


class _ListIter(io.DataIter):
    """The given batches, in order, once an epoch."""

    def __init__(self, batches):
        super().__init__(batch_size=batches[0].data[0].shape[0])
        self._batches, self._cur = batches, 0
        self.provide_data = batches[0].provide_data
        self.provide_label = batches[0].provide_label

    def reset(self):
        self._cur = 0

    def next(self):
        if self._cur >= len(self._batches):
            raise StopIteration
        self._cur += 1
        return self._batches[self._cur - 1]


_PS_DIM, _PS_BATCH = 20, 16
_PS_OPT = {"learning_rate": 0.1, "momentum": 0.9}


def _ps_sym(hidden=32):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=hidden)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _ps_batches(k, seed=0, dim=_PS_DIM, bucket_keys=None):
    """k batches, no two alike."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(k):
        shape = (_PS_BATCH, dim) if bucket_keys is None \
            else (_PS_BATCH, bucket_keys[i])
        out.append(io.DataBatch(
            data=[mx.nd.array(rng.randn(*shape).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 10, _PS_BATCH)
                               .astype(np.float32))],
            bucket_key=None if bucket_keys is None else bucket_keys[i],
            provide_data=[io.DataDesc("data", shape)],
            provide_label=[io.DataDesc("softmax_label", (_PS_BATCH,))]))
    return out


def _ps_module(ctx, hidden=32):
    """Bound, seeded, optimizer ready: two of these start bitwise alike."""
    mod = Module(_ps_sym(hidden), context=ctx)
    mod.bind(data_shapes=[("data", (_PS_BATCH, _PS_DIM))],
             label_shapes=[("softmax_label", (_PS_BATCH,))])
    mod.init_params(mx.init.Xavier())
    rng = np.random.RandomState(11)
    args = {n: mx.nd.array(rng.randn(*a.shape).astype(np.float32) * 0.05)
            for n, a in sorted(mod._exec.arg_dict.items())
            if n not in ("data", "softmax_label")}
    mod.set_params(args, {}, allow_missing=True, force_init=True)
    mod.init_optimizer(optimizer="sgd", optimizer_params=_PS_OPT)
    return mod


def _ps_fit(mod, batches, callback=None):
    mod.fit(_ListIter(batches), num_epoch=1, optimizer="sgd",
            optimizer_params=_PS_OPT, batch_end_callback=callback)


def _ps_counts():
    from mxnet_tpu import telemetry as tm
    return (tm.counter("executor/prestage_hits_total").value,
            tm.counter("executor/prestage_misses_total").value)


def _ps_state(mod):
    """Parameters and optimizer state, as host arrays."""
    arg_p, aux_p = mod.get_params()
    out = dict(("arg:" + k, v.asnumpy()) for k, v in arg_p.items())
    out.update(("aux:" + k, v.asnumpy()) for k, v in aux_p.items())
    for i, st in mod._updater.states.items():
        for j, a in enumerate(mx.optimizer.fused_state_arrays(st)):
            out["state:%d:%d" % (i, j)] = a.asnumpy()
    return out


def _ps_assert_same_state(a, b):
    sa, sb = _ps_state(a), _ps_state(b)
    assert sorted(sa) == sorted(sb) and any(k.startswith("state:")
                                            for k in sa)
    for k in sa:
        assert np.array_equal(sa[k], sb[k]), k


def _ps_manual(mod, batches):
    """forward_backward / update over the batches: never calls prepare."""
    outs = []
    for db in batches:
        mod.forward_backward(db)
        mod.update()
        outs.append(mod.get_outputs()[0].asnumpy())
    return outs


def _check_fit_is_the_manual_loop(ctx, k=6):
    batches = _ps_batches(k)
    fitted, manual = _ps_module(ctx), _ps_module(ctx)
    seen = []
    h0, m0 = _ps_counts()
    _ps_fit(fitted, batches, lambda p: seen.append(
        p.locals["self"].get_outputs()[0].asnumpy()))
    h1, m1 = _ps_counts()
    want = _ps_manual(manual, batches)
    h2, m2 = _ps_counts()
    # every batch of the epoch, the first included, was prepared before
    # its step: K hits an input, no miss; the manual loop never hits; both
    # count one put an input a step (docs/observability.md)
    assert (h1 - h0, m1 - m0) == (2 * k, 0)
    assert (h2 - h1, m2 - m1) == (0, 2 * k)
    assert len(seen) == k
    for got, ref in zip(seen, want):
        assert np.array_equal(got, ref)
    assert not np.array_equal(want[0], want[1])
    _ps_assert_same_state(fitted, manual)
    assert fitted._exec._prestaged == {}
    return fitted


def test_prestage_fit_is_bitwise_the_manual_loop():
    _check_fit_is_the_manual_loop(mx.cpu())


class _StopFit(Exception):
    pass


def _check_forward_after_fit_was_cut(ctx, hidden):
    """The benchmark's sequence: a callback ends fit (the next batch is
    pre-staged by then), then the driver forwards a batch of its own."""
    from mxnet_tpu import programs
    batches = _ps_batches(6)
    other = _ps_batches(1, seed=99)[0]
    mod = _ps_module(ctx, hidden)

    def stop(param):
        if param.nbatch == 2:
            raise _StopFit()

    with pytest.raises(_StopFit):
        _ps_fit(mod, batches, stop)
    exe = mod._exec
    # batch 3 was placed for a step that never ran, and nothing is deferred
    assert sorted(exe._prestaged) == ["data", "softmax_label"]
    assert exe._prestaged["data"][0] is batches[3].data[0]._data
    assert mod._fused_batch is None
    fresh = _ps_module(ctx, hidden)
    fresh.set_params(*mod.get_params(), force_init=True)

    def kinds():
        out = {}
        for rec in programs.entries().values():
            out[rec["kind"]] = out.get(rec["kind"], 0) + 1
        return out

    before = kinds()
    h0, m0 = _ps_counts()
    for is_train in (False, True):
        mod.forward(other, is_train=is_train)
        got = mod.get_outputs()[0].asnumpy()
        assert exe._prestaged == {}
        fresh.forward(other, is_train=is_train)
        assert np.array_equal(got, fresh.get_outputs()[0].asnumpy())
    assert _ps_counts() == (h0, m0 + 8)          # two inputs, four calls
    after = kinds()
    # the two forward programs and nothing else: no backward (the unfused
    # replay of a deferred batch), no second fused step
    assert after.get("executor_forward", 0) \
        - before.get("executor_forward", 0) <= 2
    for kind in ("executor_vjp", "fused_step"):
        assert after.get(kind, 0) == before.get(kind, 0), kind
    assert exe.arg_dict["data"]._data is not batches[3].data[0]._data
    assert np.array_equal(exe.arg_dict["data"].asnumpy(),
                          other.data[0].asnumpy())


def test_prestage_forward_after_fit_was_cut_sees_its_own_batch():
    _check_forward_after_fit_was_cut(mx.cpu(), hidden=37)


def _check_batch_written_after_prepare(ctx):
    first, new = _ps_batches(2, seed=5)
    mod, ref = _ps_module(ctx), _ps_module(ctx)
    mod.prepare(first)
    first.data[0][:] = new.data[0]           # in place: a new buffer
    h0, m0 = _ps_counts()
    mod.forward_backward(first)
    mod.update()
    # the data is placed anew (its prepared copy holds the old values),
    # the untouched label is bound from the look-aside
    assert _ps_counts() == (h0 + 1, m0 + 1)
    ref.forward_backward(io.DataBatch(data=new.data, label=first.label))
    ref.update()
    assert np.array_equal(mod.get_outputs()[0].asnumpy(),
                          ref.get_outputs()[0].asnumpy())
    _ps_assert_same_state(mod, ref)


def test_prestage_batch_written_after_prepare_misses():
    _check_batch_written_after_prepare(mx.cpu())


def test_prestage_callbacks_see_the_steps_own_batch_and_outputs():
    """Inside a callback ``data_batch`` is still step N's (batch N+1 is
    fetched and prepared by then) and ``get_outputs()`` is step N's,
    without a compile: the unfused replay did not run."""
    from mxnet_tpu import telemetry as tm
    batches = _ps_batches(6)
    want = _ps_manual(_ps_module(mx.cpu()), batches)
    mod = _ps_module(mx.cpu())
    compiles = []

    def watch(param):
        assert param.locals["data_batch"] is batches[param.nbatch]
        if param.nbatch + 1 < len(batches):
            assert param.locals["next_data_batch"] \
                is batches[param.nbatch + 1]
        before = tm.compile_count()
        got = param.locals["self"].get_outputs()[0].asnumpy()
        assert tm.compile_count() == before
        assert np.array_equal(got, want[param.nbatch])
        compiles.append(tm.compile_count())

    _ps_fit(mod, batches, watch)
    assert len(compiles) == len(batches)
    assert len(set(compiles[1:])) == 1       # the warm steps compile nothing


def test_prestage_reshape_between_prepare_and_step_misses():
    batch, = _ps_batches(1, seed=7)
    mod, ref = _ps_module(mx.cpu()), _ps_module(mx.cpu())
    mod.prepare(batch)
    assert sorted(mod._exec._prestaged) == ["data", "softmax_label"]
    mod.reshape(batch.provide_data, batch.provide_label)
    assert mod._exec._prestaged == {}
    h0, m0 = _ps_counts()
    mod.forward_backward(batch)
    mod.update()
    assert _ps_counts() == (h0, m0 + 2)
    _ps_manual(ref, [batch])
    _ps_assert_same_state(mod, ref)
    data = mod._exec.arg_dict["data"]._data
    assert data.committed and data.devices() == {mx.cpu().jax_device()}


def test_prestage_across_bucket_switches():
    """BucketingModule.prepare binds the next batch's bucket, lets that
    bucket's module pre-stage it, and switches back: the metric and the
    callbacks still read the step that ran."""
    keys = [16, 8, 8, 16, 8, 16]

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        pooled = mx.sym.mean(data, axis=1, keepdims=True)
        fc = mx.sym.FullyConnected(pooled, num_hidden=10, name="fc")
        return (mx.sym.SoftmaxOutput(fc, name="softmax"), ("data",),
                ("softmax_label",))

    def module():
        mod = mx.module.BucketingModule(sym_gen, default_bucket_key=16,
                                        context=mx.cpu())
        mod.bind(data_shapes=[("data", (_PS_BATCH, 16))],
                 label_shapes=[("softmax_label", (_PS_BATCH,))])
        mod.init_params(mx.init.Xavier())
        mod.set_params({"fc_weight": mx.nd.array(np.full((10, 1), 0.05,
                                                         np.float32)),
                        "fc_bias": mx.nd.zeros((10,))}, {},
                       force_init=True)
        mod.init_optimizer(optimizer="sgd", optimizer_params=_PS_OPT)
        return mod

    batches = _ps_batches(len(keys), seed=3, bucket_keys=keys)
    fitted, manual = module(), module()
    seen = []
    h0, m0 = _ps_counts()

    def watch(param):
        me = param.locals["self"]
        assert me._curr_bucket_key == keys[param.nbatch]
        seen.append(me.get_outputs()[0].asnumpy())

    _ps_fit(fitted, batches, watch)
    h1, m1 = _ps_counts()
    assert (h1 - h0, m1 - m0) == (2 * len(keys), 0)
    want = []
    for db in batches:
        manual.forward_backward(db)
        manual.update()
        want.append(manual.get_outputs()[0].asnumpy())
    for got, ref in zip(seen, want):
        assert np.array_equal(got, ref)
    pa, pb = fitted.get_params()[0], manual.get_params()[0]
    for k in pa:
        assert np.array_equal(pa[k].asnumpy(), pb[k].asnumpy()), k
