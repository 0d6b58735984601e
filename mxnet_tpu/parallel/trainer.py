"""Fused, sharded training steps.

Reference analog: the whole of SURVEY §3.4's hot loop —
Module.forward_backward + kvstore push/pull + optimizer update — fused
into ONE compiled XLA program. The reference amortizes per-op dispatch
with engine bulking (MXNET_EXEC_BULK_*, graph_executor.cc:673) and runs
gradient aggregation through KVStore/NCCL; here the entire step (forward,
backward, SGD update, and — under a mesh — the gradient all-reduce that
GSPMD derives from the shardings) is a single jit, so per-step Python
overhead is one dispatch regardless of model depth.

Parallelism axes:
- dp: batch dim sharded; grads all-reduce over ICI (GSPMD-inserted).
- tp: large weight matrices sharded on a hidden dim; matmuls become
  partial-matmul + collective, XLA chooses reduce-scatter/all-gather.
Sequence (sp) and pipeline (pp) axes live in mxnet_tpu.parallel.sequence /
.pipeline (transformer-oriented); this trainer covers the image-classifier
path the reference benchmarks.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..symbol.symbol import _graph_eval_fn, _topo
from ..ops import registry as _reg

__all__ = ["make_train_step", "ShardedTrainer"]


def _loss_and_probs(outputs, label):
    """Cross-entropy value from SoftmaxOutput probs (the reference computes
    metric-side CE the same way; the gradient comes from the op's own
    custom vjp)."""
    import jax.numpy as jnp
    probs = outputs[0]
    li = label.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, li[:, None], axis=1)[:, 0]
    return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-10)))


def make_train_step(symbol, data_name="data", label_name="softmax_label",
                    lr=0.05, momentum=0.9, wd=0.0, compute_dtype=None):
    """Build ``step(params, moms, aux, data, label, key) ->
    (params, moms, aux, loss)`` as one pure function.

    Gradients are taken with a ones-cotangent on output 0, matching
    executor.backward for the *Output loss heads (their custom vjp carries
    the real loss gradient).

    ``compute_dtype="bfloat16"`` enables mixed precision: master params
    stay fp32, the forward/backward graph runs in bf16 (conv/matmul hit
    the MXU at 2x fp32 rate), gradients are accumulated back into fp32
    for the update — the capability analog of the reference's
    multi-precision fp16 mode (python/mxnet/optimizer.py
    multi_precision)."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.dtype(compute_dtype) if compute_dtype else None

    fn = _graph_eval_fn(symbol, is_train=True)
    arg_names = symbol.list_arguments()
    param_names = [n for n in arg_names if n not in (data_name, label_name)]

    def step(params, moms, aux, data, label, key):
        def fwd(p):
            if cdt is not None:
                p = {k: v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating)
                     else v for k, v in p.items()}
            env = dict(p)
            env.update(aux)
            env[data_name] = data.astype(cdt) if cdt is not None else data
            env[label_name] = label
            outs, new_aux = fn(env, key)
            outs = tuple(o.astype(jnp.float32) for o in outs)
            new_aux = {k: v.astype(jnp.float32) for k, v in new_aux.items()}
            return outs, new_aux

        (outs, new_aux), vjp = jax.vjp(fwd, params)
        cts = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        # unused aux cotangents are zero
        aux_cts = {k: jnp.zeros(v.shape, v.dtype) for k, v in new_aux.items()}
        (grads,) = vjp((cts, aux_cts))
        loss = _loss_and_probs(outs, label)

        new_params = {}
        new_moms = {}
        for n in param_names:
            g = grads[n] + wd * params[n]
            if momentum > 0.0:
                m = momentum * moms[n] + g
                new_moms[n] = m
            else:
                m = g
                new_moms[n] = moms[n]
            new_params[n] = params[n] - lr * m
        return new_params, new_moms, new_aux, loss

    return step, param_names


class ShardedTrainer(object):
    """Data(+tensor)-parallel trainer over a device mesh.

    The capability-equivalent of DataParallelExecutorGroup + KVStore
     `device`/`dist_tpu_sync` (executor_group.py:143, kvstore_nccl.h),
    expressed as shardings: batch split over ``dp_axis``, optionally large
    weights split over ``tp_axis``; XLA inserts the collectives.
    """

    def __init__(self, symbol, mesh, data_name="data",
                 label_name="softmax_label", lr=0.05, momentum=0.9, wd=0.0,
                 dp_axis="dp", tp_axis=None, tp_min_size=2048,
                 compute_dtype=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._symbol = symbol
        self._mesh = mesh
        self._data_name = data_name
        self._label_name = label_name
        self._dp_axis = dp_axis
        self._tp_axis = tp_axis
        self._tp_min_size = tp_min_size
        step, self._param_names = make_train_step(
            symbol, data_name, label_name, lr=lr, momentum=momentum, wd=wd,
            compute_dtype=compute_dtype)
        self._aux_names = symbol.list_auxiliary_states()
        self._step_raw = step
        self._jitted = None
        self._multi_jitted = None
        self._param_shardings = None

    # -- sharding rules ----------------------------------------------------
    def _shard_param(self, name, shape):
        """TP rule: shard the largest divisible dim of big matrices over
        tp_axis; everything else replicated (grads then allreduce over dp
        only, the dist_tpu_sync layout)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._mesh
        if self._tp_axis and self._tp_axis in mesh.axis_names:
            tp = mesh.shape[self._tp_axis]
            size = int(_np.prod(shape)) if shape else 0
            if size >= self._tp_min_size and len(shape) >= 2:
                dims = sorted(range(len(shape)), key=lambda i: -shape[i])
                for d in dims:
                    if shape[d] % tp == 0 and shape[d] >= tp * 2:
                        spec = [None] * len(shape)
                        spec[d] = self._tp_axis
                        return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    def _data_sharding(self, ndim):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(mesh := self._mesh,
                             P(self._dp_axis, *([None] * (ndim - 1))))

    # -- param init --------------------------------------------------------
    def init(self, data_shape, label_shape, initializer=None, seed=0):
        """Infer shapes, initialize params on the mesh with the declared
        shardings (device_put once; resharded training state stays put)."""
        import jax
        import jax.numpy as jnp
        from ..initializer import Xavier, InitDesc
        initializer = initializer or Xavier(magnitude=2.0)
        kwargs = {self._data_name: data_shape, self._label_name: label_shape}
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        arg_names = self._symbol.list_arguments()
        shape_of = dict(zip(arg_names, arg_shapes))
        import numpy as np
        from ..ndarray.ndarray import NDArray, zeros as nd_zeros

        params = {}
        self._param_shardings = {}
        for n in self._param_names:
            shp = shape_of[n]
            host = nd_zeros(shp)
            initializer(InitDesc(n), host)
            sh = self._shard_param(n, shp)
            self._param_shardings[n] = sh
            params[n] = jax.device_put(host._data, sh)
        moms = {n: jax.device_put(jnp.zeros_like(params[n]),
                                  self._param_shardings[n])
                for n in self._param_names}
        aux = {}
        from jax.sharding import NamedSharding, PartitionSpec as P
        for n, shp in zip(self._aux_names, aux_shapes):
            init_val = jnp.ones(shp, jnp.float32) if n.endswith("_var") \
                else jnp.zeros(shp, jnp.float32)
            aux[n] = jax.device_put(init_val, NamedSharding(self._mesh, P()))
        return params, moms, aux

    # -- compiled step -----------------------------------------------------
    def _compile(self, data_ndim):
        """One jit for the whole step. Input arrays carry their shardings
        (device_put at init/step), GSPMD propagates them and inserts the
        collectives; params/momenta/aux buffers are donated so the update
        is in-place at the XLA level (the analog of the reference's
        in-place optimizer kernels)."""
        import jax
        if self._jitted is None:
            self._jitted = jax.jit(self._step_raw, donate_argnums=(0, 1, 2))
        return self._jitted

    def stage(self, data, label):
        """Pre-stage a batch on the mesh with the dp sharding (one H2D
        copy). ``step`` detects already-staged arrays and skips the
        per-call transfer — the analog of the reference's --benchmark mode
        reusing one synthetic device-resident batch, and of real input
        pipelines that prefetch H2D ahead of the step."""
        import jax
        import jax.numpy as jnp
        data = jnp.asarray(data, dtype=jnp.float32)
        label = jnp.asarray(label, dtype=jnp.float32)
        return (jax.device_put(data, self._data_sharding(data.ndim)),
                jax.device_put(label, self._data_sharding(1)))

    def _stacked_sharding(self, ndim):
        """Sharding for a (k, batch, ...) stack of batches: scan axis
        replicated, batch axis dp-sharded."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh,
                             P(None, self._dp_axis, *([None] * (ndim - 2))))

    def stage_many(self, data, label):
        """Stage ``k`` distinct batches stacked on a leading axis —
        ``data`` (k, batch, ...), ``label`` (k, batch) — for
        :meth:`run_steps`. One H2D copy for the whole stack."""
        import jax
        import jax.numpy as jnp
        data = jnp.asarray(data, dtype=jnp.float32)
        label = jnp.asarray(label, dtype=jnp.float32)
        return (jax.device_put(data, self._stacked_sharding(data.ndim)),
                jax.device_put(label, self._stacked_sharding(2)))

    def run_steps(self, params, moms, aux, data, label, key=None):
        """Run ``k`` fused steps as ONE compiled program — a
        ``lax.scan`` over the leading axis of pre-staged stacked batches
        (``data`` (k, batch, ...) from :meth:`stage_many`).

        This is the idiomatic TPU device loop: the reference amortizes
        per-op dispatch with engine bulking (graph_executor.cc:673
        MXNET_EXEC_BULK_*); here k whole steps share one dispatch, so
        host per-call latency is paid once per k steps instead of
        once per step. Training state is donated (in-place update chain
        on device). Returns ``(params, moms, aux, last_loss)``."""
        import jax
        from .. import random as _random
        if key is None:
            key = _random.next_key()
        if self._multi_jitted is None:
            import jax.numpy as jnp
            from jax import lax
            raw = self._step_raw

            def multi(params, moms, aux, data, label, key):
                k = data.shape[0]

                def body(carry, xs):
                    p, m, a = carry
                    d, l, i = xs
                    p, m, a, loss = raw(p, m, a, d, l,
                                        jax.random.fold_in(key, i))
                    return (p, m, a), loss

                (p, m, a), losses = lax.scan(
                    body, (params, moms, aux),
                    (data, label, jnp.arange(k)))
                return p, m, a, losses[-1]

            self._multi_jitted = jax.jit(multi, donate_argnums=(0, 1, 2))
        return self._multi_jitted(params, moms, aux, data, label, key)

    def step(self, params, moms, aux, data, label, key=None):
        """One fused training step. ``data``/``label`` may be numpy or jax
        arrays; they are sharded over dp on the way in (no-op for arrays
        already staged via :meth:`stage`)."""
        from .. import random as _random
        if key is None:
            key = _random.next_key()
        data, label = self.stage(data, label)
        fn = self._compile(data.ndim)
        return fn(params, moms, aux, data, label, key)
