"""Gluon Trainer.

Reference: python/mxnet/gluon/trainer.py (kvstore setup :158-211,
step :254, _update :347).

Applies an Optimizer to a set of Parameters after ``autograd.backward``,
optionally synchronizing gradients through a KVStore (allreduce over the
device mesh / processes for ``device`` / ``dist_tpu_sync`` types).
"""
from __future__ import annotations

from ..base import MXNetError
from .. import optimizer as opt_mod
from ..kvstore import KVStore, create as kv_create
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


class Trainer(object):
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got %s." % type(params))
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise ValueError("got %s instead of Parameter" % type(p))
            self._params.append(p)
            self._param2idx[p.name] = i
        self._compression_params = compression_params
        self._contains_sparse = any(p.stype != "default" for p in self._params)
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params and list(optimizer_params) != ["rescale_grad"]:
                raise ValueError(
                    "optimizer_params must be None if optimizer is an "
                    "instance of Optimizer instead of str")
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._optimizer.param_dict = param_dict
        self._states = [self._optimizer.create_state_multi_precision(
            i, p.data()) if p._data is not None else None
            for i, p in enumerate(self._params)]

    def _init_kvstore(self):
        if self._kvstore_type:
            kv = self._kvstore_type
            self._kvstore = kv if isinstance(kv, KVStore) else kv_create(kv)
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = False
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    self._kvstore.init(i, p.data())
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
        else:
            self._kvstore = None
            self._update_on_kvstore = False
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def optimizer(self):
        return self._optimizer

    def _ensure_states(self):
        for i, p in enumerate(self._params):
            if self._states[i] is None and p._data is not None:
                self._states[i] = \
                    self._optimizer.create_state_multi_precision(i, p.data())

    def allreduce_grads(self):
        """Reduce gradients over devices/workers without updating
        (reference: trainer.py allreduce_grads)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        for i, p in enumerate(self._params):
            if p.grad_req != "null":
                self._kvstore.push(i, p.grad(), priority=-i)
                self._kvstore.pull(i, p.grad(), priority=-i,
                                   ignore_sparse=False)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + optimizer update
        (reference: trainer.py:254 step)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._ensure_states()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def update(self, batch_size, ignore_stale_grad=False):
        """Optimizer update only — caller did allreduce_grads
        (reference: trainer.py update)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._ensure_states()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        items = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            if p._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        "Parameter %s has not been initialized" % p.name)
                continue
            items.append((i, p.data(), p.grad(), self._states[i]))
        # Fused path: every parameter's update in ONE donated XLA program
        # (a single Python→XLA dispatch) instead of one kernel dispatch
        # per parameter. fused_apply declines (→ per-param fallback) for
        # sparse grads, multi-precision, optimizers without a pure rule,
        # dist_* kvstores, or MXNET_FUSED_STEP=0.
        if items and self._fused_apply_ok() \
                and opt_mod.fused_apply(self._optimizer, items):
            return
        for i, weight, grad, state in items:
            self._optimizer.update_multi_precision(i, weight, grad, state)

    def _fused_apply_ok(self):
        from ..model import fused_step_supported
        return fused_step_supported(self._optimizer, self._kvstore,
                                    self._update_on_kvstore,
                                    self._compression_params)

    def save_states(self, fname):
        """Reference: trainer.py save_states."""
        import pickle
        with open(fname, "wb") as f:
            states = []
            for s in self._states:
                states.append(_state_to_numpy(s))
            pickle.dump({"optimizer": self._optimizer.__class__.__name__,
                         "num_update": self._optimizer.num_update,
                         "states": states}, f)

    def load_states(self, fname):
        import pickle
        from ..ndarray.ndarray import array
        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self._ensure_states()
        self._optimizer.num_update = blob.get("num_update", 0)
        self._states = [_state_from_numpy(s) for s in blob["states"]]


def _state_to_numpy(s):
    from ..ndarray.ndarray import NDArray
    if s is None:
        return None
    if isinstance(s, NDArray):
        return s.asnumpy()
    if isinstance(s, (list, tuple)):
        return [_state_to_numpy(x) for x in s]
    return s


def _state_from_numpy(s):
    import numpy as np
    from ..ndarray.ndarray import array
    if s is None:
        return None
    if isinstance(s, np.ndarray):
        return array(s, dtype=s.dtype)
    if isinstance(s, list):
        return [_state_from_numpy(x) for x in s]
    return s
