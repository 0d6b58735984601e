"""NDArray: the user-visible tensor.

Reference: include/mxnet/ndarray.h:82 + python/mxnet/ndarray/ndarray.py.

TPU-native design: an NDArray owns a ``jax.Array``. The reference's
dependency-engine asynchrony (engine vars, WaitToRead/WaitToWrite,
SURVEY.md §1 layer 2/4) maps directly onto PjRt's async buffer semantics —
every op returns immediately with a future-backed buffer and
``wait_to_read`` is ``block_until_ready``. Write-after-read hazards cannot
occur because buffers are immutable: "mutation" (``x += 1``, sliced
assignment, optimizer updates) swaps the underlying buffer, which is the
functional equivalent of the engine's version-counter protocol
(src/engine/threaded_engine.h:99-218).
"""
from __future__ import annotations

import contextlib as _contextlib
import numpy as _np

from ..base import MXNetError, np_dtype, numeric_types

_NULL_SCOPE = _contextlib.nullcontext()
from ..context import Context, current_context
from .. import random as _random
from .. import telemetry as _tm
from ..ops import registry as _reg

__all__ = ["NDArray", "invoke_op", "array", "zeros", "ones", "full", "empty",
           "arange", "concat", "stack", "waitall"]


def _jnp():
    import jax.numpy as jnp
    return jnp


class NDArray:
    """A multi-dimensional array on a device (reference: ndarray.h:82)."""

    __slots__ = ("_data", "_ctx", "grad", "_grad_req", "_ag_node",
                 "_fresh_grad", "__weakref__")

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self.grad = None
        self._grad_req = None
        self._ag_node = None

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        return invoke_op("transpose", [self], {})

    # -- synchronization (reference: WaitToRead / MXNDArrayWaitAll) --------
    def wait_to_read(self):
        self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    # -- host transfer -----------------------------------------------------
    def asnumpy(self):
        """Copy to host; the sync point (reference: ndarray.py asnumpy).

        Under multi-host training (``dist_tpu_sync``) an array can span
        processes; the host copy is then this process's addressable
        view — the full value for replicated arrays (params, optimizer
        state), the local rows for batch-sharded ones."""
        data = self._data
        if getattr(data, "is_fully_addressable", True) is False:
            from ..parallel.mesh import host_local_value
            data = host_local_value(data)
        return _np.asarray(data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            self.asnumpy(), "x".join(str(s) for s in self.shape), self._ctx)

    # -- dtype / device movement ------------------------------------------
    def astype(self, dtype, copy=True):
        if not copy and self.dtype == np_dtype(dtype):
            return self
        return invoke_op("Cast", [self], {"dtype": np_dtype(dtype).name})

    def copy(self):
        return invoke_op("_copy", [self], {})

    def copyto(self, other):
        if isinstance(other, NDArray):
            data, home = self._data, other._home()
            if home is not None and data.is_fully_addressable:
                import jax
                data = jax.device_put(data, home)
            other._set_data(data)
            return other
        if isinstance(other, Context):
            return NDArray(_device_put(self._data, other), ctx=other)
        raise TypeError("copyto does not support type %s" % type(other))

    def _home(self):
        """Where this array's buffer belongs: the mesh placement it
        already holds (a dp mesh replicated or sharded it), else its
        context's device. None for a global array of a multi-process
        mesh: its mesh places it (Executor._dp_place), not a context."""
        data = self._data
        if not data.is_fully_addressable:
            return None
        held = data.sharding
        return held if len(held.device_set) > 1 else self._ctx.jax_device()

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return NDArray(_device_put(self._data, ctx), ctx=ctx)

    as_in_ctx = as_in_context

    def detach(self):
        # a COPY, not a buffer alias: in this framework an alias never
        # observes in-place updates anyway (ops rebind, reference:
        # functional XLA semantics), and sharing the buffer would let a
        # later donating optimizer update (ops/registry.py) invalidate
        # the detached snapshot
        return NDArray(self._data.copy(), ctx=self._ctx)

    # -- autograd ----------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Mark for gradient computation (reference: autograd.mark_variables)."""
        from .. import autograd
        autograd.mark_variable(self, grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- internal mutation (buffer swap = new engine var version) ----------
    def _set_data(self, new_jax_array):
        self._data = new_jax_array

    # -- shape ops ---------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return invoke_op("Reshape", [self],
                         {"shape": shape, "reverse": kwargs.get("reverse", False)})

    def reshape_like(self, other):
        return invoke_op("reshape_like", [self, other], {})

    def expand_dims(self, axis):
        return invoke_op("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke_op("squeeze", [self], {"axis": axis})

    def flatten(self):
        return invoke_op("Flatten", [self], {})

    def transpose(self, axes=None):
        return invoke_op("transpose", [self], {"axes": axes})

    def swapaxes(self, dim1, dim2):
        return invoke_op("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def broadcast_to(self, shape):
        return invoke_op("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke_op("broadcast_like", [self, other], {})

    def tile(self, reps):
        return invoke_op("tile", [self], {"reps": tuple(reps)})

    def repeat(self, repeats, axis=None):
        return invoke_op("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, mode, pad_width, constant_value=0.0):
        return invoke_op("Pad", [self], {"mode": mode, "pad_width": pad_width,
                                         "constant_value": constant_value})

    def slice_axis(self, axis, begin, end):
        return invoke_op("slice_axis", [self],
                         {"axis": axis, "begin": begin, "end": end})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke_op("SliceChannel", [self],
                         {"num_outputs": num_outputs, "axis": axis,
                          "squeeze_axis": squeeze_axis})

    def slice(self, begin, end, step=None):
        """Reference: ndarray slice method (tensor/matrix_op.cc slice)."""
        attrs = {"begin": tuple(begin), "end": tuple(end)}
        if step is not None:
            attrs["step"] = tuple(step)
        return invoke_op("slice", [self], attrs)

    def take(self, indices, axis=0, mode="clip"):
        return invoke_op("take", [self, indices], {"axis": axis, "mode": mode})

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return invoke_op("one_hot", [self], {"depth": depth, "on_value": on_value,
                                             "off_value": off_value})

    # -- reductions --------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke_op("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke_op("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke_op("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke_op("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke_op("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke_op("norm", [self], {"ord": ord, "axis": axis,
                                          "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke_op("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke_op("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke_op("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke_op("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke_op("topk", [self], {"axis": axis, "k": k,
                                          "ret_typ": ret_typ,
                                          "is_ascend": is_ascend})

    def clip(self, a_min, a_max):
        return invoke_op("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke_op("abs", [self], {})

    def sign(self):
        return invoke_op("sign", [self], {})

    def sqrt(self):
        return invoke_op("sqrt", [self], {})

    def square(self):
        return invoke_op("square", [self], {})

    def exp(self):
        return invoke_op("exp", [self], {})

    def log(self):
        return invoke_op("log", [self], {})

    def relu(self):
        return invoke_op("relu", [self], {})

    def sigmoid(self):
        return invoke_op("sigmoid", [self], {})

    def tanh(self):
        return invoke_op("tanh", [self], {})

    def softmax(self, axis=-1):
        return invoke_op("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke_op("log_softmax", [self], {"axis": axis})

    def zeros_like(self):
        return invoke_op("zeros_like", [self], {})

    def ones_like(self):
        return invoke_op("ones_like", [self], {})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke_op("dot", [self, other],
                         {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError("sparse stype %r pending" % stype)
        return self

    # -- arithmetic dunders ------------------------------------------------
    def _binop(self, other, op_name, scalar_op_name, reverse_scalar=None):
        if isinstance(other, NDArray):
            return invoke_op(op_name, [self, other], {})
        if isinstance(other, numeric_types):
            return invoke_op(scalar_op_name, [self], {"scalar": float(other)})
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binop(other, "broadcast_sub", "_rminus_scalar")

    def __mul__(self, other):
        return self._binop(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binop(other, "broadcast_div", "_rdiv_scalar")

    def __mod__(self, other):
        return self._binop(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        return self._binop(other, "broadcast_mod", "_rmod_scalar")

    def __pow__(self, other):
        return self._binop(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binop(other, "broadcast_power", "_rpower_scalar")

    def __neg__(self):
        return invoke_op("negative", [self], {})

    def __abs__(self):
        return invoke_op("abs", [self], {})

    def __eq__(self, other):
        if other is None:
            return False
        return self._binop(other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        if other is None:
            return True
        return self._binop(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binop(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binop(other, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binop(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binop(other, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def __iadd__(self, other):
        out = self.__add__(other)
        self._set_data(out._data)
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self._set_data(out._data)
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self._set_data(out._data)
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._set_data(out._data)
        return self

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            return invoke_op("take", [self, key], {"axis": 0, "mode": "clip"})
        from ..ops.matrix import encode_index_key
        enc = encode_index_key(key)
        if enc is not None:
            # basic indexing routes through the op registry so it lands
            # on the autograd tape (reference records slice ops too)
            return invoke_op("_getitem", [self], {"key": enc})
        out = self._data[key]
        return NDArray(out, ctx=self._ctx)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, numeric_types):
            pass
        elif isinstance(value, _np.ndarray):
            value = _jnp().asarray(value, dtype=self.dtype)
        if isinstance(key, NDArray):
            key = key._data
        if isinstance(key, slice) and key == slice(None):
            new = _jnp().broadcast_to(
                _jnp().asarray(value, dtype=self.dtype), self.shape)
        else:
            new = self._data.at[key].set(value)
        home = self._home()
        if home is not None:
            # assignment never moves the array: a fill value computed on
            # the default device lands, committed, where the buffer lives
            import jax
            new = jax.device_put(new, home)
        self._set_data(new)

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]


def _device_put(data, ctx):
    import jax
    return jax.device_put(data, ctx.jax_device())


# ---------------------------------------------------------------------------
# op invocation (the analog of MXImperativeInvokeEx → Imperative::Invoke,
# reference call stack SURVEY.md §3.1)
# ---------------------------------------------------------------------------

def invoke_op(name, inputs, attrs, out=None):
    """Invoke a registered op on NDArray inputs.

    1. unwraps jax arrays; 2. threads a PRNG key for rng ops; 3. runs the
    jitted kernel (async dispatch — control returns before compute ends);
    4. records on the autograd tape when recording; 5. applies in-place
    semantics for mutating ops; 6. wraps outputs.
    """
    op = _reg.get_op(name)
    from .. import autograd

    # Thread the runtime train/predict mode into ops that declare a
    # ``train_mode`` attr (Dropout, BatchNorm, RNN) unless the caller passed
    # one explicitly — the analog of the reference's thread-local
    # ``is_training_`` flag (include/mxnet/imperative.h:148-153).
    if "train_mode" in op.attr_defaults and (attrs is None
                                             or "train_mode" not in attrs):
        attrs = dict(attrs or {})
        attrs["train_mode"] = autograd.is_training()

    arrays = [x._data if isinstance(x, NDArray) else x for x in inputs]

    ctx = None
    for x in inputs:
        if isinstance(x, NDArray):
            ctx = x._ctx
            break
    if ctx is None:
        # creation op: it allocates on the destination's context (the
        # reference allocates on ctx), computed THERE — a cpu() array
        # never touches the chip, an initializer filling a tpu() array
        # never detours through the host
        first = out[0] if isinstance(out, (list, tuple)) else out
        ctx = first._ctx if first is not None else current_context()
        import jax
        dev = ctx.jax_device()
        device_scope = jax.default_device(dev)
    else:
        dev = None
        device_scope = _NULL_SCOPE

    key = None
    if op.needs_rng:
        with device_scope:
            key = _random.next_key()
        arrays = [key] + arrays

    from .. import engine as _engine
    if _engine.profiling_imperative():
        from .. import profiler as _prof
        prof_scope = _prof.scope(name, "operator")
    else:
        prof_scope = _NULL_SCOPE   # singleton: keep the hot path light
    tm_token = _tm.dispatch_begin() if _tm._enabled else None
    with prof_scope, device_scope:
        raw_out = _reg.invoke_raw(op, arrays, attrs)
        if _engine.is_naive():
            # NaiveEngine debug mode: serialize every op (reference:
            # src/engine/naive_engine.cc, MXNET_ENGINE_TYPE)
            for o in raw_out:
                o.block_until_ready()
    if tm_token is not None:
        _tm.dispatch_end(name, tm_token)
    if dev is not None:
        # commit: an uncommitted result would follow whatever committed
        # array it meets next, host batch included
        raw_out = tuple(jax.device_put(o, dev) for o in raw_out)

    if op.mutate_inputs:
        for out_i, in_i in enumerate(op.mutate_inputs):
            tgt = inputs[in_i]
            tgt._set_data(raw_out[out_i])
        return inputs[op.mutate_inputs[0]]

    outputs = tuple(NDArray(o, ctx=ctx) for o in raw_out)

    if autograd.is_recording() and op.differentiable:
        autograd.record_op(op, attrs, inputs, outputs, key=key)

    if out is not None:
        tgts = out if isinstance(out, (list, tuple)) else [out]
        for t, o in zip(tgts, outputs):
            t._set_data(o._data)
        return out

    if len(outputs) == 1:
        return outputs[0]
    return list(outputs)


# ---------------------------------------------------------------------------
# creation helpers (reference: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    import jax
    ctx = ctx or current_context()
    from_typed = isinstance(source_array, (NDArray, _np.ndarray))
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    np_arr = _np.asarray(source_array)
    if dtype is None:
        # match reference: dtype follows a typed source, else float32
        # (python/mxnet/ndarray/ndarray.py array())
        if from_typed and np_arr.dtype != _np.float64:
            dtype = np_arr.dtype
        else:
            dtype = _np.float32
    np_arr = np_arr.astype(np_dtype(dtype), copy=False)
    return NDArray(jax.device_put(np_arr, ctx.jax_device()), ctx=ctx)


def zeros(shape, ctx=None, dtype=None, **_kw):
    ctx = ctx or current_context()
    with ctx:
        return invoke_op("_zeros", [], {"shape": _as_shape(shape),
                                        "dtype": np_dtype(dtype).name})


def ones(shape, ctx=None, dtype=None, **_kw):
    ctx = ctx or current_context()
    with ctx:
        return invoke_op("_ones", [], {"shape": _as_shape(shape),
                                       "dtype": np_dtype(dtype).name})


def full(shape, val, ctx=None, dtype=None, **_kw):
    ctx = ctx or current_context()
    with ctx:
        return invoke_op("_full", [], {"shape": _as_shape(shape), "value": val,
                                       "dtype": np_dtype(dtype).name})


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    ctx = ctx or current_context()
    with ctx:
        return invoke_op("_arange", [], {"start": start, "stop": stop,
                                         "step": step, "repeat": repeat,
                                         "dtype": np_dtype(dtype).name})


def concat(*arrays, dim=1):
    return invoke_op("Concat", list(arrays), {"dim": dim})


def stack(*arrays, axis=0):
    return invoke_op("stack", list(arrays), {"axis": axis})


def _as_shape(shape):
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def waitall():
    """Block until all launched work completes (reference: MXNDArrayWaitAll,
    engine WaitForAll). Blocks on every live jax.Array — the PjRt analog of
    draining the dependency engine — then on any pending effects. Surfaces
    deferred device errors at this sync point, matching the reference's
    exception-propagation-to-sync contract
    (src/engine/threaded_engine.cc:474-476)."""
    import jax
    for arr in jax.live_arrays():
        arr.block_until_ready()
    jax.effects_barrier()
