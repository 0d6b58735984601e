"""Fused optimizer-update Pallas kernels (SGD-momentum and Adam).

The forensics boundary report attributes the train step's residual HBM
round-trips to the update tail: XLA fuses the elementwise update math
well enough, but each param's weight / grad / momentum / variance makes
its own trip through HBM per fused-multiply stage. Here the whole
update rule runs as one VMEM-resident kernel per parameter block —
weight and state tiles are loaded once, updated in registers, and
written back in place (the outputs alias the weight/state inputs, so on
TPU the update is a true in-place donation like the surrounding fused
step).

Bitwise contract: the kernel body *is* the optimizer's own pure-lax
``fused_rule`` evaluated on VMEM refs — there is no reimplementation to
drift. Off-TPU the dispatchers run the lax rule directly (the tier-1
path, so tier-1 training numerics are bitwise-unchanged by
construction); ``interpret=True`` forces the Pallas interpreter for
parity tests. Interpret-mode parity is ULP-bounded, not bitwise:
XLA:CPU's FMA-contraction choices depend on operand shape and layout,
and the interpreter's ref plumbing changes them — the tests pin the
kernel to within a few ULPs of the jitted twin.

Hyperparameters arrive as a packed f32 SMEM vector, so LR-schedule
steps change data, not trace constants — zero recompiles across
schedule updates, same weak-type discipline as ``executor`` fused
steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import on_tpu

__all__ = ["sgd_fused_update", "adam_fused_update"]

# kernel-contract registry: exported kernel -> module-level pure-lax
# twin (see tools/check_pallas_contracts.py)
PALLAS_KERNELS = {
    "sgd_fused_update": "_sgd_fused_xla",
    "adam_fused_update": "_adam_fused_xla",
}

_LANES = 128


def _sgd_fused_xla(w, g, state, h):
    """Pure-lax twin: the optimizer's own ``_sgd_fused`` rule."""
    from ...optimizer import _sgd_fused
    return _sgd_fused(w, g, state, h)


def _adam_fused_xla(w, g, state, h):
    """Pure-lax twin: the optimizer's own ``_adam_fused`` rule."""
    from ...optimizer import _adam_fused
    return _adam_fused(w, g, state, h)


def _update_kernel(h_ref, w_ref, g_ref, *refs, rule, n_state,
                   hyper_keys):
    """One row-block of the update: rebuild the hyper dict from SMEM
    scalars (key *presence* — e.g. ``clip_gradient`` — is static via
    ``hyper_keys``; values are data) and evaluate the optimizer's lax
    rule on the VMEM tiles."""
    h = {k: h_ref[i] for i, k in enumerate(hyper_keys)}
    state = tuple(refs[i][:] for i in range(n_state))
    w_new, s_new = rule(w_ref[:], g_ref[:], state, h)
    refs[n_state][:] = w_new
    for i, s in enumerate(s_new):
        refs[n_state + 1 + i][:] = s


@functools.partial(jax.jit, static_argnames=("rule", "hyper_keys",
                                             "block_rows", "interpret"))
def _fused_update(rule, hv, w, g, state, hyper_keys, block_rows,
                  interpret):
    shape, dtype = w.shape, w.dtype
    n = max(1, int(np.prod(shape)))
    rows = -(-n // _LANES)
    rows = -(-rows // 8) * 8      # f32 sublane multiple

    def _flat(x):
        x = x.reshape(-1)
        return jnp.pad(x, (0, rows * _LANES - n)).reshape(rows, _LANES)

    wf, gf = _flat(w), _flat(g)
    sf = tuple(_flat(s) for s in state)
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    n_state = len(sf)
    bspec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_update_kernel, rule=rule,
                               n_state=n_state, hyper_keys=hyper_keys)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
                 + [bspec] * (2 + n_state),
        out_specs=[bspec] * (1 + n_state),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), dtype)]
                  * (1 + n_state),
        # weight/state tiles update in place (operands: hv=0, w=1,
        # g=2, state=3..)
        input_output_aliases=dict(
            [(1, 0)] + [(3 + i, 1 + i) for i in range(n_state)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(hv, wf, gf, *sf)
    w_new = out[0].reshape(-1)[:n].reshape(shape)
    s_new = tuple(o.reshape(-1)[:n].reshape(shape) for o in out[1:])
    return w_new, s_new


def _fused_update_dispatch(rule, w, g, state, h, block_rows, interpret):
    if interpret is None:
        if not on_tpu(w):
            return rule(w, g, tuple(state), h)
        interpret = False
    hyper_keys = tuple(sorted(h))
    hv = jnp.stack([jnp.asarray(h[k], jnp.float32).reshape(())
                    for k in hyper_keys])
    return _fused_update(rule, hv, w, g, tuple(state), hyper_keys,
                         int(block_rows), bool(interpret))


def sgd_fused_update(w, g, state, h, block_rows=256, interpret=None):
    """SGD(-momentum) update as a single VMEM-resident kernel.

    ``state`` is ``(momentum,)`` or ``()`` (stateless SGD); ``h`` is
    the fused-rule hyper dict (``lr``, ``wd``, ``rescale_grad``,
    optionally ``momentum`` / ``clip_gradient``). Returns
    ``(w_new, state_new)`` exactly like ``optimizer._sgd_fused``, which
    is the bitwise twin and the off-TPU path."""
    return _fused_update_dispatch(_sgd_fused_xla, w, g, state, h,
                                  block_rows, interpret)


def adam_fused_update(w, g, state, h, block_rows=256, interpret=None):
    """Adam update as a single VMEM-resident kernel.

    ``state`` is ``(mean, var)``; ``h`` is the fused-rule hyper dict
    (``lr``, ``wd``, ``beta1``/``one_minus_beta1``,
    ``beta2``/``one_minus_beta2``, ``epsilon``, ``rescale_grad``,
    optionally ``clip_gradient``). Returns ``(w_new, (mean, var))``
    exactly like ``optimizer._adam_fused``, which is the bitwise twin
    and the off-TPU path."""
    return _fused_update_dispatch(_adam_fused_xla, w, g, state, h,
                                  block_rows, interpret)
