"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference has NO pipeline parallelism (SURVEY.md §2.3 marks it
absent — its engine's async dataflow overlaps ops but never splits a
model into device stages). This is the TPU-first addition SURVEY §2.3
prescribes: each device on the ``pp`` mesh axis owns one *stage* of a
homogeneous stack (e.g. transformer blocks); microbatches stream
through the ring, activations hop stage-to-stage with ``lax.ppermute``
over ICI, and the whole schedule is one ``lax.scan`` inside
``shard_map`` — so XLA sees a static program and overlaps each stage's
matmuls with the neighbour transfers.

Schedule: classic fill-drain (GPipe). ``T = M + S - 1`` ticks for M
microbatches over S stages; bubble fraction = (S-1)/T. The whole thing
is differentiable — ``jax.grad`` through it yields the reverse
pipeline schedule automatically.

Constraints (inherent to scan-based pipelining): every stage maps an
activation of shape (mb, ...) to the same shape; stage parameters are
a pytree stacked on a leading ``num_stages`` axis (sharded P('pp')).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(params_list):
    """Stack per-stage pytrees into one pytree with a leading stage axis
    (shard this axis over the ``pp`` mesh dimension)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def _pipeline_local(params, x_mb, *, stage_fn, axis, num_stages,
                    num_microbatches):
    """Per-device body. params: (1, ...) local stage slice (already
    sharded by shard_map); x_mb: (M, mb, ...) full microbatch stream
    (replicated)."""
    params = jax.tree_util.tree_map(lambda p: p[0], params)
    idx = jax.lax.axis_index(axis)
    S, M = num_stages, num_microbatches
    T = M + S - 1
    mb_shape = x_mb.shape[1:]

    is_first = idx == 0
    is_last = idx == S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        state, out_buf = carry
        # stage 0 ingests microbatch t (while t < M); others take the
        # activation handed over from the previous stage last tick.
        feed_idx = jnp.clip(t, 0, M - 1)
        feed = jax.lax.dynamic_index_in_dim(x_mb, feed_idx, axis=0,
                                            keepdims=False)
        inp = jnp.where(is_first, feed, state)
        out = stage_fn(params, inp)
        # last stage: microbatch (t - S + 1) completes at tick t
        mb_done = t - (S - 1)
        valid = jnp.logical_and(is_last, mb_done >= 0)
        onehot = (jnp.arange(M) == mb_done).astype(out.dtype)
        upd = onehot.reshape((M,) + (1,) * len(mb_shape)) * out[None]
        out_buf = out_buf + jnp.where(valid, upd, jnp.zeros_like(upd))
        # hand this tick's activation to the next stage over ICI
        state = jax.lax.ppermute(out, axis, perm)
        return (state, out_buf), None

    state0 = jnp.zeros(mb_shape, x_mb.dtype)
    buf0 = jnp.zeros((M,) + mb_shape, x_mb.dtype)
    (_, out_buf), _ = jax.lax.scan(tick, (state0, buf0), jnp.arange(T))
    # only the last stage holds real outputs; sum over the axis
    # replicates them everywhere.
    return jax.lax.psum(out_buf, axis)


def pipeline_apply(stage_params, x, stage_fn, mesh=None, axis="pp",
                   num_microbatches=None):
    """Run ``x`` through a pipelined stack of stages.

    Parameters
    ----------
    stage_params : pytree with leading axis ``num_stages`` (see
        :func:`stack_stage_params`); sharded P(axis) over the mesh.
    x : (batch, ...) input; batch must divide into microbatches.
    stage_fn : ``stage_fn(stage_param_slice, act) -> act`` with identical
        activation shapes in and out.
    num_microbatches : default = number of stages (bubble ≈ 50%); raise
        it (e.g. 4×stages) to shrink the bubble.

    Returns (batch, ...) outputs, replicated over the axis.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("pipeline_apply needs a Mesh (parallel.make_mesh)")
    S = mesh.shape[axis]
    M = num_microbatches or S
    if x.shape[0] % M:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (x.shape[0], M))
    mb = x.shape[0] // M
    x_mb = x.reshape((M, mb) + x.shape[1:])

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn, axis=axis,
                          num_stages=S, num_microbatches=M),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stage_params, x_mb)
    return out.reshape((M * mb,) + out.shape[2:])
