"""Declarative operator registry.

TPU-native replacement for the reference's nnvm op registry
(reference: src/operator/*, registration pattern at
src/operator/nn/fully_connected.cc:239-328 and attribute types at
include/mxnet/op_attr_types.h:198-301).

Design: every operator is a *pure JAX function*
``fn(*arrays, **attrs) -> array | tuple`` registered with metadata.
There are no hand-written FInferShape / FInferType / FGradient tables:

* shape & dtype inference  -> ``jax.eval_shape`` on the pure function
  (replaces src/executor/infer_graph_attr_pass.cc);
* gradients                -> ``jax.vjp`` on the pure function
  (replaces per-op FGradient registrations);
* kernel fusion & memory   -> XLA compilation of the jitted function
  (replaces PlanMemory / engine op bulking, src/executor/graph_executor.cc:637,673).

Eager invocation jits each (op, attrs) pair once and relies on JAX's
shape-keyed compile cache — the analog of the reference's CachedOp-style
amortization of per-op dispatch overhead (SURVEY.md §3.1).
"""
from __future__ import annotations

import functools
import threading

from ..base import MXNetError, canonical_attrs

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke", "invoke_raw", "alias"]

_REGISTRY: dict = {}
_local = threading.local()


class OpDef:
    """Metadata for one operator.

    Parameters
    ----------
    name : canonical op name (MXNet-compatible, e.g. ``FullyConnected``).
    fn : pure JAX function ``fn(*arrays, **attrs)``.
    num_outputs : static int, or callable(attrs)->int for variadic ops
        (e.g. ``split``).
    needs_rng : if True, ``fn`` takes a leading PRNG ``key`` array argument
        supplied by the runtime (replaces the reference's per-device
        RandGenerator resource, include/mxnet/random_generator.h).
    mutate_inputs : indices of inputs updated in place at the NDArray layer
        (optimizer update ops — reference: src/operator/optimizer_op.cc).
    differentiable : False for integer-output / discrete ops.
    attr_defaults : dict of attr name -> default, used by frontend codegen.
    """

    __slots__ = ("name", "fn", "num_outputs", "needs_rng", "mutate_inputs",
                 "differentiable", "attr_defaults", "doc")

    def __init__(self, name, fn, num_outputs=1, needs_rng=False,
                 mutate_inputs=(), differentiable=True, attr_defaults=None,
                 doc=None):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.needs_rng = needs_rng
        self.mutate_inputs = tuple(mutate_inputs)
        self.differentiable = differentiable
        self.attr_defaults = dict(attr_defaults or {})
        self.doc = doc or (fn.__doc__ if fn else None)

    def n_outputs(self, attrs) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name, **kwargs):
    """Decorator: register a pure JAX function as an operator."""
    def _wrap(fn):
        if name in _REGISTRY:
            raise MXNetError("operator %r already registered" % name)
        _REGISTRY[name] = OpDef(name, fn, **kwargs)
        return fn
    return _wrap


def alias(new_name, existing_name):
    """Register ``new_name`` as an alias of an existing op."""
    op = get_op(existing_name)
    _REGISTRY[new_name] = OpDef(new_name, op.fn, num_outputs=op.num_outputs,
                                needs_rng=op.needs_rng,
                                mutate_inputs=op.mutate_inputs,
                                differentiable=op.differentiable,
                                attr_defaults=op.attr_defaults, doc=op.doc)
    return _REGISTRY[new_name]


def get_op(name) -> OpDef:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError("operator %r is not registered" % name) from None


def list_ops():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# eager invocation with per-(op, attrs) jit cache
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted(name, attr_key, donate_ok=False):
    import jax
    op = _REGISTRY[name]
    attrs = dict(attr_key)

    def _call(*arrays):
        return op.fn(*arrays, **attrs)

    donate = ()
    if donate_ok and op.mutate_inputs:
        # in-place ops (optimizer updates): donate the mutated buffers so
        # XLA aliases them input->output — a true on-device in-place
        # update with no double-buffering, the analog of the reference's
        # kWriteInplace (include/mxnet/op_attr_types.h OpReqType).
        # The NDArray layer rebinds the same NDArray to the output;
        # invoke_raw only passes donate_ok while no unfreed tape exists,
        # so no stale backward can read the donated buffer.
        shift = 1 if op.needs_rng else 0
        donate = tuple(i + shift for i in op.mutate_inputs)

    return jax.jit(_call, donate_argnums=donate)


def _donation_allowed(op):
    if not op.mutate_inputs:
        return False
    from ..config import get as _cfg
    if not _cfg("MXNET_UPDATE_BUFFER_DONATION"):
        return False
    from .. import autograd
    return not autograd.has_live_tape()


def invoke_raw(op: OpDef, arrays, attrs):
    """Apply an op to raw jax arrays, returning a tuple of jax arrays.

    Inside an outer trace (jit / grad) this inlines; eagerly it hits the
    jit cache keyed on (name, attrs) + JAX's own shape/dtype cache.
    """
    fn = _jitted(op.name, canonical_attrs(attrs), _donation_allowed(op))
    out = fn(*arrays)
    if isinstance(out, (tuple, list)):
        return tuple(out)
    return (out,)


def invoke(name, arrays, attrs=None):
    """Convenience: invoke by name on raw jax arrays."""
    return invoke_raw(get_op(name), arrays, attrs or {})
