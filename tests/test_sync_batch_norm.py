"""SyncBatchNorm tests (reference:
src/operator/contrib/sync_batch_norm-inl.h — cross-device moment sync).
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import io
from mxnet_tpu.module import Module


def _bn_sym(op):
    data = mx.sym.Variable("data")
    net = op(data, name="sbn", fix_gamma=False, momentum=0.5)
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_sync_bn_matches_bn_single_device():
    x = np.random.RandomState(0).randn(8, 3, 5, 5).astype(np.float32)
    y = np.zeros((8,), np.float32)
    outs = []
    for op in (mx.sym.BatchNorm, mx.sym.SyncBatchNorm):
        mod = Module(_bn_sym(op), context=mx.cpu(0))
        mod.bind(data_shapes=[("data", x.shape)],
                 label_shapes=[("softmax_label", (8,))])
        mod.init_params(mx.init.One())
        mod.forward(io.DataBatch(data=[mx.nd.array(x)],
                                 label=[mx.nd.array(y)]), is_train=True)
        outs.append(mod.get_outputs()[0].asnumpy())
        aux = {n: a.asnumpy() for n, a in mod._exec.aux_dict.items()}
        assert any("moving_mean" in n for n in aux)
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_sync_bn_global_stats_under_dp_mesh():
    """Under the 4-device dp Module, batch moments are computed over the
    GLOBAL batch — the defining property of SyncBatchNorm. The moving-mean
    aux after one step must reflect the full-batch mean on every device."""
    rng = np.random.RandomState(1)
    # device-dependent distribution: each quarter of the batch has a
    # different mean, so per-device stats would differ from global stats
    x = np.concatenate([rng.randn(2, 3, 4, 4) + 4 * i for i in range(4)],
                       axis=0).astype(np.float32)
    y = np.zeros((8,), np.float32)
    mod = Module(_bn_sym(mx.sym.SyncBatchNorm),
                 context=[mx.cpu(i) for i in range(4)])
    mod.bind(data_shapes=[("data", x.shape)],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.One())
    mod.forward(io.DataBatch(data=[mx.nd.array(x)],
                             label=[mx.nd.array(y)]), is_train=True)
    aux = {n: a.asnumpy() for n, a in mod._exec.aux_dict.items()}
    mm = [v for n, v in aux.items() if "moving_mean" in n][0]
    global_mean = x.mean(axis=(0, 2, 3))
    # momentum 0.5 from zero init -> new_mm = 0.5*0 + 0.5*batch_mean
    np.testing.assert_allclose(mm, 0.5 * global_mean, rtol=1e-4, atol=1e-5)


def test_sync_bn_axis_name_shard_map():
    """Explicit-collective path: under shard_map with a mapped batch axis,
    axis_name pmeans the moments so every shard normalizes with global
    stats."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.ops import registry as reg

    op = reg.get_op("SyncBatchNorm")
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    rng = np.random.RandomState(2)
    x = rng.randn(8, 3, 4, 4).astype(np.float32)
    gamma = np.ones(3, np.float32)
    beta = np.zeros(3, np.float32)
    mm = np.zeros(3, np.float32)
    mv = np.ones(3, np.float32)

    def f(xs):
        return op.fn(xs, gamma, beta, mm, mv, train_mode=True,
                     fix_gamma=False, axis_name="dp")

    sharded = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = np.asarray(jax.jit(sharded)(x))
    ref = np.asarray(op.fn(x, gamma, beta, mm, mv, train_mode=True,
                           fix_gamma=False))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
