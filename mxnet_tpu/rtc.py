"""RTC: runtime kernel compilation (Pallas analog of NVRTC).

Reference: src/common/rtc.cc:35-61 + python/mxnet/rtc.py (CudaModule:
compile CUDA C at runtime, get_kernel(name, signature), launch on
NDArrays with grid/block dims).

TPU-native: the runtime-compiled kernel language is **Pallas**. A
``PallasModule`` takes Python source defining one or more Pallas kernel
functions (``def kernel(in_ref, ..., out_ref): ...``); ``get_kernel``
wraps one of them into a launchable bound to output shapes/specs, and
``Kernel.launch`` runs it on NDArrays through ``pl.pallas_call`` (jit
compiled on first launch, cached after — the Mosaic pipeline replaces
NVRTC). Off-TPU the kernel runs in pallas interpreter mode so the same
source is testable anywhere.
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["PallasModule", "Kernel"]


class PallasModule(object):
    """Compile Pallas kernel source at runtime (reference: rtc.py
    CudaModule; `exports` kept for API parity)."""

    def __init__(self, source, options=(), exports=()):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        self._namespace = {"jax": jax, "jnp": jnp, "pl": pl,
                           "pltpu": pltpu}
        if isinstance(source, str):
            exec(compile(source, "<rtc>", "exec"), self._namespace)
        elif callable(source):
            self._namespace[source.__name__] = source
        else:
            raise MXNetError("source must be Python source text or a "
                             "kernel function")
        self.exports = tuple(exports)

    def get_kernel(self, name, signature=None):
        """Look up a kernel function and wrap it (the ``signature``
        string of the reference's cuda path is accepted and ignored —
        shapes/dtypes come from the launch arguments)."""
        fn = self._namespace.get(name)
        if fn is None or not callable(fn):
            raise MXNetError("kernel %r not found in module" % name)
        return Kernel(fn, name)


class Kernel(object):
    """A launchable Pallas kernel (reference: rtc.py Kernel.launch)."""

    def __init__(self, fn, name):
        self._fn = fn
        self.name = name
        self._cache = {}

    def launch(self, args, ctx=None, grid=None, out_shapes=None,
               interpret=None):
        """Run the kernel. ``args``: NDArrays (all inputs; outputs are
        returned). ``out_shapes``: list of (shape, dtype) for outputs,
        default = first input's. ``grid``: optional pallas grid."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from .ndarray.ndarray import NDArray

        arrays = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                  for a in args]
        if out_shapes is None:
            out_shapes = [(arrays[0].shape, arrays[0].dtype)]
        if interpret is None:
            from .ops.pallas.flash_attention import on_tpu
            interpret = not all(on_tpu(a) for a in arrays)
        key = (tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
               tuple((tuple(s), str(d)) for s, d in out_shapes),
               grid, interpret)
        call = self._cache.get(key)
        if call is None:
            out_sds = [jax.ShapeDtypeStruct(tuple(s), d)
                       for s, d in out_shapes]
            kwargs = {"out_shape": out_sds[0] if len(out_sds) == 1
                      else out_sds, "interpret": interpret}
            if grid is not None:
                kwargs["grid"] = grid
            call = jax.jit(lambda *xs: pl.pallas_call(self._fn, **kwargs)(*xs))
            self._cache[key] = call
        out = call(*arrays)
        if isinstance(out, (tuple, list)):
            return [NDArray(o) for o in out]
        return NDArray(out)
