"""Optimizer classes driving the fused update operators.

Reference: python/mxnet/optimizer.py:444-1498 (17 optimizers, registry,
Updater for kvstore-side application). The update math lives in
mxnet_tpu/ops/optimizer_ops.py as single fused XLA kernels (the analog of
src/operator/optimizer_op.cc, where "update IS an operator" so the whole
step is one engine op); these classes own the bookkeeping: lr/wd
schedules, per-param multipliers, update counts, state creation, and
multi-precision (bf16/fp16 weights with fp32 master copies).
"""
from __future__ import annotations

import logging
import pickle

import numpy

from .base import MXNetError
from .ndarray.ndarray import NDArray, zeros
from .ndarray import register as _register_mod  # noqa: F401  (op funcs)
from . import ndarray as nd

__all__ = ["Optimizer", "SGD", "Signum", "NAG", "Adam", "AdaGrad", "RMSProp",
           "AdaDelta", "Ftrl", "FTML", "Adamax", "Nadam", "SGLD", "DCASGD",
           "Test", "Updater", "get_updater", "create", "register",
           "fused_apply", "fused_state_arrays", "pack_fused_hyper",
           "unpack_fused_hyper"]


# ---------------------------------------------------------------------------
# fused functional update rules
#
# Each rule is a PURE function ``rule(weight, grad, state, hyper) ->
# (new_weight, new_state)`` over raw jax arrays: ``state`` is a tuple of
# state arrays (possibly empty), ``hyper`` a dict of 0-d arrays in the
# weight's dtype. The scalars of ALL parameters cross to the device as
# ONE float32 array a step (``pack_fused_hyper``; a python scalar handed
# to jit is a transfer of its own) and the traced program takes each
# parameter's dict back out of its row (``unpack_fused_hyper``). They
# are data, so a changing learning-rate schedule (or rescale_grad per
# batch size) NEVER retriggers XLA compilation; only the key set is
# static. The rules mirror the fused kernels in ops/optimizer_ops.py
# op for op, and every scalar-scalar expression the kernels fold in python
# (e.g. Adam's ``1 - beta1``) is folded HOST-side into ``hyper`` here, so
# a fused train step is bitwise-identical to the unfused
# forward/vjp/per-param-kernel sequence (asserted by
# tests/test_fused_step.py). Every rule is elementwise over operands of
# ONE shape and nothing else: XLA's own fusion then reads and writes
# weight, gradient and state where they lie, in whatever layout they
# have, in place under the step's donation. A rule that flattens, pads
# or reshapes its operands pays a physical relayout of each on the TPU
# (a (512, 512, 3, 3) weight flattened is a transpose with a minor
# dimension of 3); tests/test_fused_step.py holds every rule to that.
# ---------------------------------------------------------------------------

def _rule_prep(g, h):
    """grad * rescale_grad (+ optional clip) — mirrors optimizer_ops
    ``_prep_grad``. Clip PRESENCE is static (pytree structure); its value
    is traced."""
    import jax.numpy as jnp
    g = g * h["rescale_grad"]
    if "clip_gradient" in h:
        g = jnp.clip(g, -h["clip_gradient"], h["clip_gradient"])
    return g


def _sgd_fused(w, g, state, h):
    g = _rule_prep(g, h)
    if state:
        mom = h["momentum"] * state[0] - h["lr"] * (g + h["wd"] * w)
        return w + mom, (mom,)
    return w - h["lr"] * (g + h["wd"] * w), ()


def _nag_fused(w, g, state, h):
    if state:
        g = _rule_prep(g, h) + h["wd"] * w
        mom = h["momentum"] * state[0] + g
        return w - h["lr"] * (g + h["momentum"] * mom), (mom,)
    g = _rule_prep(g, h)
    return w - h["lr"] * (g + h["wd"] * w), ()


def _signum_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h)
    if state:
        mom = h["momentum"] * state[0] - h["one_minus_momentum"] * g
        wn = (h["wdlh_coef"] * w + h["lr"] * jnp.sign(mom)
              - h["lr_wd"] * w)
        return wn, (mom,)
    return w - h["lr"] * (jnp.sign(g) + h["wd"] * w), ()


def _adam_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h) + h["wd"] * w
    mean, var = state
    mean_new = h["beta1"] * mean + h["one_minus_beta1"] * g
    var_new = h["beta2"] * var + h["one_minus_beta2"] * jnp.square(g)
    return (w - h["lr"] * mean_new / (jnp.sqrt(var_new) + h["epsilon"]),
            (mean_new, var_new))


def _adagrad_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h)
    hist = state[0] + g * g
    div = g / (jnp.sqrt(hist) + h["eps"])
    return w - h["lr"] * (div + w * h["wd"]), (hist,)


def _rmsprop_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h) + h["wd"] * w
    if len(state) == 1:                       # plain (Tieleman)
        n_new = h["gamma1"] * state[0] + h["one_minus_gamma1"] * jnp.square(g)
        wn = w - h["lr"] * g / jnp.sqrt(n_new + h["epsilon"])
        if "clip_weights" in h:
            wn = jnp.clip(wn, -h["clip_weights"], h["clip_weights"])
        return wn, (n_new,)
    n, g_acc, delta = state                   # centered (Graves)
    n_new = h["gamma1"] * n + h["one_minus_gamma1"] * jnp.square(g)
    g_acc_new = h["gamma1"] * g_acc + h["one_minus_gamma1"] * g
    delta_new = h["gamma2"] * delta - h["lr"] * g / jnp.sqrt(
        n_new - jnp.square(g_acc_new) + h["epsilon"])
    wn = w + delta_new
    if "clip_weights" in h:
        wn = jnp.clip(wn, -h["clip_weights"], h["clip_weights"])
    return wn, (n_new, g_acc_new, delta_new)


def _adadelta_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h)
    acc_g, acc_delta = state
    acc_g_new = h["rho"] * acc_g + h["one_minus_rho"] * g * g
    cd = (jnp.sqrt(acc_delta + h["epsilon"])
          / jnp.sqrt(acc_g_new + h["epsilon"])) * g
    acc_delta_new = h["rho"] * acc_delta + h["one_minus_rho"] * cd * cd
    return w - cd - h["wd"] * w, (acc_g_new, acc_delta_new)


def _ftrl_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h)
    z, n = state
    n_new = n + jnp.square(g)
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / h["lr"]
    z_new = z + g - sigma * w
    wn = jnp.where(
        jnp.abs(z_new) <= h["lamda1"], jnp.zeros_like(w),
        -(z_new - jnp.sign(z_new) * h["lamda1"])
        / ((h["beta"] + jnp.sqrt(n_new)) / h["lr"] + h["wd"]))
    return wn, (z_new, n_new)


def _ftml_fused(w, g, state, h):
    import jax.numpy as jnp
    g = _rule_prep(g, h) + h["wd"] * w
    d, v, z = state
    v_new = h["beta2"] * v + h["one_minus_beta2"] * jnp.square(g)
    d_new = h["d_coef"] * (jnp.sqrt(v_new / h["v_coef"]) + h["epsilon"])
    sigma = d_new - h["beta1"] * d
    z_new = h["beta1"] * z + h["one_minus_beta1"] * g - sigma * w
    return -z_new / d_new, (d_new, v_new, z_new)


def _adamax_fused(w, g, state, h):
    import jax.numpy as jnp
    g = g * h["rescale_grad"] + h["wd"] * w
    if "clip_gradient" in h:
        g = jnp.clip(g, -h["clip_gradient"], h["clip_gradient"])
    m, u = state
    m_new = h["beta1"] * m + h["one_minus_beta1"] * g
    u_new = jnp.maximum(h["beta2"] * u, jnp.abs(g))
    return w - h["lr"] * m_new / u_new, (m_new, u_new)


def _test_fused(w, g, state, h):
    return (w - h["lr"] * g * h["rescale_grad"], (state[0] + g,))


def pack_fused_hyper(hypers):
    """Per-parameter hyper dicts (``Optimizer.fused_hyper``) -> ``(keys,
    float32 [n, k])``: one row a parameter, one column a sorted key. The
    array is the ONE leaf the scalars add to a jitted program's
    arguments; ``keys`` is static (it belongs in the program's cache
    key). Every dict must hold the same keys — nothing is padded."""
    keys = tuple(sorted(hypers[0])) if hypers else ()
    for h in hypers:
        if h.keys() != hypers[0].keys():
            raise MXNetError(
                "fused hyper-parameter keys differ between parameters: "
                "%s vs %s" % (sorted(h), list(keys)))
    rows = [[h[k] for k in keys] for h in hypers]
    return keys, numpy.asarray(rows, numpy.float32).reshape(
        len(hypers), len(keys))


def unpack_fused_hyper(row, keys, dtype):
    """Inside the trace: one row of the packed array -> the ``{key: 0-d
    array}`` dict a fused rule reads. A python float entered jit WEAK
    and took the weight's dtype in every product; an element of a
    float32 array is strong and would promote a bfloat16/float16 update
    to float32 (and break the donation of its buffers), so the row is
    cast to a floating ``dtype`` first — a no-op for float32."""
    import jax.numpy as jnp
    if jnp.issubdtype(dtype, jnp.floating):
        row = row.astype(dtype)
    return {k: row[j] for j, k in enumerate(keys)}


def fused_state_arrays(state):
    """Normalize an optimizer state (None | NDArray | tuple) to the flat
    tuple of NDArray buffers a fused rule consumes/produces."""
    if state is None:
        return ()
    if isinstance(state, NDArray):
        return (state,)
    return tuple(state)


class Optimizer(object):
    """Base optimizer (reference: python/mxnet/optimizer.py:444)."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        """Register a subclass under its lowercased name."""
        assert isinstance(klass, type)
        name = klass.__name__.lower()
        if name in Optimizer.opt_registry:
            logging.warning("New optimizer %s is overriding existing "
                            "optimizer %s", klass.__name__, name)
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict), \
            "param_idx2name should be a dict of param indexes to names."
        self.idx2name = param_idx2name.copy()
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None else ()
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    def create_state(self, index, weight):
        """Create auxiliary state for the given weight. Override."""

    def create_state_multi_precision(self, index, weight):
        """Low-precision weights get an fp32 master copy when
        multi_precision is on; state layout is (state, weight32)."""
        if self.multi_precision and weight.dtype == numpy.float16:
            weight_master_copy = weight.astype(numpy.float32)
            return (self.create_state(index, weight_master_copy),
                    weight_master_copy)
        if weight.dtype == numpy.float16 and not self.multi_precision:
            logging.warning("Accumulating with float16 in optimizer can lead "
                            "to poor accuracy or slow convergence. Consider "
                            "using multi_precision=True option.")
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        """Update the weight given gradient and state. Override."""
        raise NotImplementedError()

    # -- fused train-step support ------------------------------------------
    def fused_rule(self):
        """Pure functional update rule for the fused train-step path
        (Executor.train_step / fused_apply):
        ``rule(weight, grad, state_tuple, hyper) -> (new_w, new_state_tuple)``
        on raw jax arrays. None (the default) = no pure rule; fused
        callers fall back to the per-param update() path."""
        return None

    def fused_hyper(self, index):
        """Per-step scalar hyperparameters for ``fused_rule``, as a dict
        of python floats (packed with every other parameter's into one
        array before they reach the device: ``pack_fused_hyper``) —
        advances the same update-count/lr-schedule bookkeeping as
        update(), so a fused and an unfused run see identical
        schedules."""
        self._update_count(index)
        h = {"lr": float(self._get_lr(index)),
             "wd": float(self._get_wd(index)),
             "rescale_grad": float(self.rescale_grad)}
        if self.clip_gradient is not None and self.clip_gradient > 0:
            h["clip_gradient"] = float(self.clip_gradient)
        return h

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == numpy.float16:
            weight_master_copy = state[1]
            grad32 = grad.astype(numpy.float32)
            self.update(index, weight_master_copy, grad32, state[0])
            weight._set_data(weight_master_copy.astype(weight.dtype)._data)
        else:
            self.update(index, weight, grad, state)

    @property
    def learning_rate(self):
        """Current learning rate incl. scheduler (reference:
        python/mxnet/optimizer.py learning_rate property)."""
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been "
                              "defined. Note that set_learning_rate can mutate "
                              "the value of the learning rate of the optimizer "
                              "only when the LRScheduler of the optimizer is "
                              "undefined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        """Set individual learning-rate multipliers for parameters."""
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        """Set individual weight-decay multipliers. By default biases and
        norm parameters (names not ending in _weight/_gamma) get wd 0."""
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def __getstate__(self):
        ret = self.__dict__.copy()
        # jitted fused-update programs are not picklable (and rebuild
        # cheaply on first use after deserialization)
        ret.pop("_fused_apply_cache", None)
        return ret

    def __setstate__(self, state):
        self.__dict__ = state


register = Optimizer.register
create = Optimizer.create_optimizer


def _common_kwargs(opt):
    kw = {"rescale_grad": opt.rescale_grad}
    if opt.clip_gradient is not None:
        kw["clip_gradient"] = opt.clip_gradient
    return kw


def _is_row_sparse(grad):
    from .ndarray.sparse import RowSparseNDArray
    return isinstance(grad, RowSparseNDArray)


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision
    (reference: optimizer.py SGD; kernels src/operator/optimizer_op.cc)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _sgd_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["momentum"] = float(self.momentum)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kw = _common_kwargs(self)
        if _is_row_sparse(grad):
            if not self.lazy_update:
                grad = grad.todense()
            else:
                # lazy path: touch only the rows present in the gradient
                # (reference: optimizer_op.cc SGDUpdateRspImpl)
                from .ops import sparse_ops as _sk
                clip = self.clip_gradient
                if state is not None:
                    w, m = _sk.rsp_sgd_mom_update(
                        weight._data, state._data, grad.indices, grad.data,
                        lr, self.momentum, wd, self.rescale_grad, clip)
                    weight._set_data(w)
                    state._set_data(m)
                else:
                    weight._set_data(_sk.rsp_sgd_update(
                        weight._data, grad.indices, grad.data, lr, wd,
                        self.rescale_grad, clip))
                return
        if state is not None:
            nd.sgd_mom_update(weight, grad, state, lr=lr, wd=wd,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, lr=lr, wd=wd, **kw)

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == numpy.float16:
            mom, w32 = state
            self._update_count(index)
            lr, wd = self._get_lr(index), self._get_wd(index)
            kw = _common_kwargs(self)
            if mom is not None:
                nd.mp_sgd_mom_update(weight, grad, mom, w32, lr=lr, wd=wd,
                                     momentum=self.momentum, **kw)
            else:
                nd.mp_sgd_update(weight, grad, w32, lr=lr, wd=wd, **kw)
        else:
            self.update(index, weight, grad, state)


@register
class Signum(Optimizer):
    """Sign-of-gradient SGD with momentum (reference: optimizer.py Signum)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _signum_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["momentum"] = float(self.momentum)
        h["one_minus_momentum"] = 1.0 - float(self.momentum)
        h["wdlh_coef"] = 1.0 - h["lr"] * float(self.wd_lh)
        h["lr_wd"] = h["lr"] * h["wd"]
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kw = _common_kwargs(self)
        if state is not None:
            nd.signum_update(weight, grad, state, lr=lr, wd=wd,
                             momentum=self.momentum, wd_lh=self.wd_lh, **kw)
        else:
            nd.signsgd_update(weight, grad, lr=lr, wd=wd, **kw)


@register
class NAG(Optimizer):
    """Nesterov accelerated gradient (reference: optimizer.py NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _nag_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["momentum"] = float(self.momentum)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kw = _common_kwargs(self)
        if state is not None:
            nd.nag_mom_update(weight, grad, state, lr=lr, wd=wd,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, lr=lr, wd=wd, **kw)


@register
class Adam(Optimizer):
    """Adam (reference: optimizer.py Adam; kernel adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def fused_rule(self):
        return _adam_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        h["lr"] = float(h["lr"] * (numpy.sqrt(coef2) / coef1))
        h["beta1"] = float(self.beta1)
        h["beta2"] = float(self.beta2)
        h["one_minus_beta1"] = 1.0 - float(self.beta1)
        h["one_minus_beta2"] = 1.0 - float(self.beta2)
        h["epsilon"] = float(self.epsilon)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= numpy.sqrt(coef2) / coef1
        mean, var = state
        if _is_row_sparse(grad):
            if not self.lazy_update:
                grad = grad.todense()
            else:
                # lazy Adam (reference: optimizer_op.cc AdamUpdateRspImpl)
                from .ops import sparse_ops as _sk
                w, m, v = _sk.rsp_adam_update(
                    weight._data, mean._data, var._data, grad.indices,
                    grad.data, lr, self.beta1, self.beta2, self.epsilon,
                    wd, self.rescale_grad, self.clip_gradient)
                weight._set_data(w)
                mean._set_data(m)
                var._set_data(v)
                return
        kw = _common_kwargs(self)
        nd.adam_update(weight, grad, mean, var, lr=lr, wd=wd,
                       beta1=self.beta1, beta2=self.beta2,
                       epsilon=self.epsilon, **kw)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: optimizer.py AdaGrad)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _adagrad_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["eps"] = float(self.float_stable_eps)
        if self.clip_gradient is not None:
            # the eager update() clips whenever clip_gradient is set
            # (not only when > 0, unlike the fused kernels)
            h["clip_gradient"] = float(self.clip_gradient)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        history = state
        history += grad * grad
        div = grad / (history.sqrt() + self.float_stable_eps)
        weight._set_data((weight - lr * (div + weight * wd))._data)


@register
class RMSProp(Optimizer):
    """RMSProp, plain (Tieleman) or centered (Graves)
    (reference: optimizer.py RMSProp; kernels rmsprop/rmspropalex_update)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # n
                    zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # g
                    zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # delta
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _rmsprop_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["gamma1"] = float(self.gamma1)
        h["one_minus_gamma1"] = 1.0 - float(self.gamma1)
        h["epsilon"] = float(self.epsilon)
        if self.centered:
            h["gamma2"] = float(self.gamma2)
        if self.clip_weights is not None and self.clip_weights > 0:
            h["clip_weights"] = float(self.clip_weights)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kw = _common_kwargs(self)
        if self.clip_weights:
            kw["clip_weights"] = self.clip_weights
        if not self.centered:
            nd.rmsprop_update(weight, grad, state, lr=lr, wd=wd,
                              gamma1=self.gamma1, epsilon=self.epsilon, **kw)
        else:
            n, g, delta = state
            nd.rmspropalex_update(weight, grad, n, g, delta, lr=lr, wd=wd,
                                  gamma1=self.gamma1, gamma2=self.gamma2,
                                  epsilon=self.epsilon, **kw)


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: optimizer.py AdaDelta)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def fused_rule(self):
        return _adadelta_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["rho"] = float(self.rho)
        h["one_minus_rho"] = 1.0 - float(self.rho)
        h["epsilon"] = float(self.epsilon)
        if self.clip_gradient is not None:
            # eager update() clips whenever clip_gradient is set
            h["clip_gradient"] = float(self.clip_gradient)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g._set_data((self.rho * acc_g + (1.0 - self.rho) * grad * grad)._data)
        current_delta = ((acc_delta + self.epsilon).sqrt()
                         / (acc_g + self.epsilon).sqrt()) * grad
        acc_delta._set_data(
            (self.rho * acc_delta
             + (1.0 - self.rho) * current_delta * current_delta)._data)
        weight._set_data((weight - current_delta - wd * weight)._data)


@register
class Ftrl(Optimizer):
    """FTRL-proximal (reference: optimizer.py Ftrl; kernel ftrl_update)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # z
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # n

    def fused_rule(self):
        return _ftrl_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        h["lamda1"] = float(self.lamda1)
        h["beta"] = float(self.beta)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        z, n = state
        kw = _common_kwargs(self)
        nd.ftrl_update(weight, grad, z, n, lr=lr, wd=wd, lamda1=self.lamda1,
                       beta=self.beta, **kw)


@register
class FTML(Optimizer):
    """FTML (reference: optimizer.py FTML; kernel ftml_update)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # d
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # v
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # z

    def fused_rule(self):
        return _ftml_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        t = self._index_update_count[index]
        # host-fold the scalar coefficients exactly as the ftml_update
        # kernel folds its python attrs, for bitwise fused/unfused parity
        h["beta1"] = float(self.beta1)
        h["one_minus_beta1"] = 1.0 - float(self.beta1)
        h["beta2"] = float(self.beta2)
        h["one_minus_beta2"] = 1.0 - float(self.beta2)
        h["epsilon"] = float(self.epsilon)
        h["d_coef"] = (1.0 - self.beta1 ** t) / h["lr"]
        h["v_coef"] = 1.0 - self.beta2 ** t
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        d, v, z = state
        kw = {"rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_grad"] = self.clip_gradient
        nd.ftml_update(weight, grad, d, v, z, lr=lr, wd=wd, beta1=self.beta1,
                       beta2=self.beta2, epsilon=self.epsilon, t=t, **kw)


@register
class Adamax(Optimizer):
    """AdaMax, Adam with infinity norm (reference: optimizer.py Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def fused_rule(self):
        return _adamax_fused

    def fused_hyper(self, index):
        h = super().fused_hyper(index)
        t = self._index_update_count[index]
        h["lr"] = float(h["lr"] / (1.0 - self.beta1 ** t))
        h["beta1"] = float(self.beta1)
        h["one_minus_beta1"] = 1.0 - float(self.beta1)
        h["beta2"] = float(self.beta2)
        if self.clip_gradient is not None:
            # eager update() clips whenever clip_gradient is set
            h["clip_gradient"] = float(self.clip_gradient)
        return h

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1.0 - self.beta1 ** t)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        m_t, u_t = state
        m_t._set_data((self.beta1 * m_t + (1.0 - self.beta1) * grad)._data)
        u_t._set_data(nd.broadcast_maximum(self.beta2 * u_t, grad.abs())._data)
        weight._set_data((weight - lr * m_t / u_t)._data)


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference: optimizer.py Nadam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t._set_data((self.beta1 * m_t + (1.0 - self.beta1) * grad)._data)
        v_t._set_data((self.beta2 * v_t + (1.0 - self.beta2) * grad * grad)._data)
        grad_prime = grad / (1.0 - self.m_schedule)
        m_t_prime = m_t / (1.0 - m_schedule_next)
        v_t_prime = v_t / (1.0 - self.beta2 ** t)
        m_t_bar = (1.0 - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight._set_data(
            (weight - lr * m_t_bar / (v_t_prime.sqrt() + self.epsilon))._data)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py SGLD)."""

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        from .ndarray import random as _ndrandom
        noise = _ndrandom.normal(0, numpy.sqrt(lr), shape=weight.shape,
                                 dtype=weight.dtype, ctx=weight.context)
        weight._set_data(
            (weight - lr / 2 * (grad + wd * weight) + noise)._data)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        delta = -lr * (grad + wd * weight + self.lamda
                       * grad * grad * (weight - previous_weight))
        if mom is not None:
            mom._set_data((mom * self.momentum + delta)._data)
            delta = mom
        previous_weight._set_data(weight._data)
        weight._set_data((weight + delta)._data)


@register
class LBSGD(Optimizer):
    """Large-Batch SGD (reference: optimizer.py:672 LBSGD).

    Per layer, gradients accumulate for ``batch_scale`` micro-batches;
    then ONE momentum-SGD step applies with the learning rate scaled by
    the warmup schedule ('linear' / 'power2' / 'sqrt' toward
    batch_scale over warmup_epochs) or by the LARS trust ratio
    sqrt(||w||^2 / (||g||^2 + wd*||w||^2)) when
    warmup_strategy='lars'. The standard recipe for scaling batch size
    with worker count — particularly relevant on pod-scale dp meshes.
    """

    def __init__(self, momentum=0.0, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32,
                 begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = int(batch_scale)
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self._cum = {}                     # index -> [cum_grad, n]

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def _warmup_mult(self, nup):
        import math
        nwup = self.warmup_epochs * self.updates_per_epoch
        maxmult = float(self.batch_scale)
        if nup >= nwup:
            return maxmult
        if nwup <= 1:
            return 1.0
        if self.warmup_strategy == "linear":
            return 1.0 + (maxmult - 1) * nup / nwup
        if self.warmup_strategy == "power2":
            return 1.0 + (maxmult - 1) * (nup * nup) / (nwup * nwup)
        if self.warmup_strategy == "sqrt":
            return 1.0 + (maxmult - 1) * math.sqrt(float(nup) / nwup)
        return 1.0

    def _lars(self, weight, grad, wd):
        import math
        w2 = float((weight * weight).asnumpy().sum())
        g2 = float((grad * grad).asnumpy().sum())
        lars = math.sqrt(w2 / (g2 + wd * w2 + 1e-18))
        return min(max(lars, 0.01), 100.0)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if _is_row_sparse(grad):
            grad = grad.todense()
        if self.batch_scale > 1:
            # accumulate per layer; the micro-batch counter is MONOTONIC
            # for the whole run (the reference's num_cums) so the warmup
            # schedule advances — only the accumulated gradient resets
            # at each macro-batch boundary
            cum = self._cum.get(index)
            if cum is None:
                self._cum[index] = cum = [grad.copy(), 1]
            elif cum[1] % self.batch_scale == 0:
                cum[0] = grad.copy()
                cum[1] += 1
            else:
                cum[0]._set_data((cum[0] + grad)._data)
                cum[1] += 1
            if cum[1] % self.batch_scale != 0:
                return                      # accumulating micro-batch
            grad = cum[0] / self.batch_scale
            nup = self.init_updates + cum[1]
        else:
            nup = self.init_updates + self.num_update
        if self.warmup_strategy == "lars":
            lr = lr * self._lars(weight, grad, wd)
        else:
            lr = lr * self._warmup_mult(nup)
        kw = _common_kwargs(self)
        if state is not None:
            nd.sgd_mom_update(weight, grad, state, lr=lr, wd=wd,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, lr=lr, wd=wd, **kw)


@register
class Test(Optimizer):
    """Test optimizer: simple accumulating SGD (reference: optimizer.py Test)."""

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_rule(self):
        return _test_fused

    def fused_hyper(self, index):
        # mirror update() exactly: raw self.lr, no scheduler/multipliers,
        # no update-count bookkeeping
        return {"lr": float(self.lr),
                "rescale_grad": float(self.rescale_grad)}

    def update(self, index, weight, grad, state):
        weight._set_data((weight - self.lr * grad * self.rescale_grad)._data)
        state._set_data((state + grad)._data)


def _colocate(state, weight):
    """Place a freshly created optimizer state where its weight actually
    lives. ``create_state`` allocates on ``weight.context`` — one device —
    but under a data-parallel mesh the weight is replicated over every
    device of the mesh, and an update over mixed placements is an error,
    not a silent copy. (A mesh spanning processes is the fused step's
    alone; Executor.train_step assembles those states itself.)"""
    if isinstance(state, NDArray):
        home = weight._home()
        if home is not None:
            import jax
            state._set_data(jax.device_put(state._data, home))
    elif isinstance(state, (tuple, list)):
        for s in state:
            _colocate(s, weight)
    return state


class Updater(object):
    """Applies an optimizer to (index, grad, weight) triples — the callable
    installed on KVStore (reference: optimizer.py Updater / get_updater)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def ensure_state(self, index, weight):
        """Lazily create (or context-sync a deserialized) state for
        ``index``; returns it. Shared by the per-param path below and the
        fused train step, so their bookkeeping can never drift."""
        if index not in self.states:
            self.states[index] = _colocate(
                self.optimizer.create_state_multi_precision(index, weight),
                weight)
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(self.states[index],
                                                         weight.context)
            self.states_synced[index] = True
        return self.states[index]

    def __call__(self, index, grad, weight):
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.ensure_state(index,
                                                                weight))

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, numpy.ndarray):
            # deserialized states arrive as numpy (get_states converts for
            # pickling); rehydrate on the weight's device
            from .ndarray.ndarray import array
            return array(state, ctx=context)
        if isinstance(state, (tuple, list)):
            return type(state)(self.sync_state_context(i, context)
                               for i in state)
        return state

    def set_states(self, states):
        """Deserialize updater state (reference: Updater.set_states)."""
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        states = {}
        for i, s in self.states.items():
            states[i] = _to_numpy_state(s)
        return pickle.dumps((states, self.optimizer) if dump_optimizer
                            else states)


def _to_numpy_state(state):
    if isinstance(state, NDArray):
        return state.asnumpy()
    if isinstance(state, (tuple, list)):
        return type(state)(_to_numpy_state(i) for i in state)
    return state


def get_updater(optimizer):
    return Updater(optimizer)


# ---------------------------------------------------------------------------
# fused whole-pytree update (one XLA program for every parameter)
# ---------------------------------------------------------------------------

def fused_apply(optimizer, items):
    """Apply ``optimizer`` to every ``(index, weight, grad, state)`` in
    ``items`` through ONE jitted XLA program with the weight and state
    buffers donated (input→output aliasing: in-place HBM update, a single
    Python→XLA dispatch instead of one per parameter — the Gluon Trainer
    analog of Executor.train_step).

    Returns True when the fused path ran (weights/states updated in
    place); False when this optimizer/configuration has no pure rule —
    the caller must then run the per-param update() path. The scalar
    hyperparameters (lr schedule, rescale_grad) of all items enter the
    program as ONE float32 array (``pack_fused_hyper``), so their value
    changes never recompile and a step hands over one array, not a
    scalar per parameter and key.
    """
    from .config import get as _cfg
    if not items or not _cfg("MXNET_FUSED_STEP"):
        return False
    rule = optimizer.fused_rule()
    if rule is None or optimizer.multi_precision:
        return False
    from .ndarray.sparse import BaseSparseNDArray
    for _i, w, g, _s in items:
        if isinstance(w, BaseSparseNDArray) or isinstance(g, BaseSparseNDArray):
            return False

    state_tuples = [fused_state_arrays(s) for (_i, _w, _g, s) in items]
    hyper_keys, hyper = pack_fused_hyper(
        [optimizer.fused_hyper(i) for (i, _w, _g, _s) in items])

    cache = optimizer.__dict__.setdefault("_fused_apply_cache", {})
    # donation honors the same knob as the per-param update kernels
    # (ops/registry.py _donation_allowed)
    donate = bool(_cfg("MXNET_UPDATE_BUFFER_DONATION"))
    cache_key = (rule, len(items), donate, hyper_keys)
    jfn = cache.get(cache_key)
    if jfn is None:
        import jax
        from .base import install_donation_warning_filter
        install_donation_warning_filter()

        def apply_all(ws, gs, ss, hs):
            new = [rule(w, g, s,
                        unpack_fused_hyper(hs[i], hyper_keys, w.dtype))
                   for i, (w, g, s) in enumerate(zip(ws, gs, ss))]
            return [n[0] for n in new], [n[1] for n in new]

        jfn = jax.jit(apply_all, donate_argnums=(0, 2) if donate else ())
        cache[cache_key] = jfn

    ws = [w._data for (_i, w, _g, _s) in items]
    gs = [g._data for (_i, _w, g, _s) in items]
    ss = [tuple(a._data for a in tup) for tup in state_tuples]

    from . import telemetry as _tm
    token = _tm.dispatch_begin() if _tm._enabled else None
    new_ws, new_ss = jfn(ws, gs, ss, hyper)
    if token is not None:
        _tm.dispatch_end("fused_optimizer_update", token)

    for (item, nw, ns, tup) in zip(items, new_ws, new_ss, state_tuples):
        item[1]._set_data(nw)
        for tgt, val in zip(tup, ns):
            tgt._set_data(val)
    return True
