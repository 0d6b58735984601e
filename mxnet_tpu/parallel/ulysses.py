"""Ulysses (all-to-all) sequence/context parallelism.

The second of the two first-class long-context strategies (the other is
``ring_attention``). The reference framework predates both — its
long-sequence story is bucketing + fused RNNs (SURVEY.md §5
"long-context"); this module is the TPU-native capability replacement,
following the DeepSpeed-Ulysses communication pattern:

- Activations arrive sequence-sharded over mesh axis ``sp``
  (each device holds (b, h, S/n, d)).
- One ``lax.all_to_all`` re-shards heads<->sequence: every device ends
  up with the FULL sequence for h/n of the heads.
- Attention for those heads runs entirely locally (the Pallas flash
  kernel or plain XLA einsum — exact global causal masking, no online
  merge needed).
- A second all_to_all restores sequence sharding.

Communication: 2 all-to-alls of the Q/K/V/O activations per attention
call — O(b·s·d·(n-1)/n²) bytes per device per all-to-all, riding ICI.
Versus the ring: fewer, larger collectives and a simpler local kernel,
but requires num_heads % n == 0 (the ring has no head constraint and
overlaps transfer with compute). Both shard the sequence axis, so
either drops into the same ``sp`` mesh axis of a 5-axis layout.

Differentiable end-to-end: ``lax.all_to_all`` is linear (its transpose
is the reverse all-to-all) and the local attention is the flash kernel
custom-vjp or pure jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..ops.pallas.flash_attention import on_tpu

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def _local_full_attention(q, k, v, causal, sm_scale, impl, interpret):
    """Full-sequence attention on local heads (runs inside shard_map)."""
    if impl == "auto":
        impl = "flash"
    if impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _ulysses_local(q, k, v, axis_name, causal, sm_scale, impl,
                   interpret=None):
    """Per-shard body: heads<->sequence all-to-all sandwich.

    In: (b, h, S/n, d) sequence-sharded. all_to_all with
    split_axis=heads, concat_axis=seq yields (b, h/n, S, d); after local
    attention the inverse all_to_all restores (b, h, S/n, d).
    """
    if interpret is None:
        interpret = not on_tpu(q)
    # split h across the axis, gather the full sequence
    qh = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    o = _local_full_attention(qh, kh, vh, causal, sm_scale, impl,
                              interpret)
    # split the sequence back, gather this shard's full head set
    return jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=False,
                      sm_scale=None, impl="auto", interpret=None):
    """All-to-all sequence-parallel attention over mesh axis ``axis``.

    q, k, v : (batch, heads, seq, head_dim); ``seq`` divisible by the
        axis size and ``heads`` divisible by the axis size (the Ulysses
        constraint — use :func:`ring_attention` when heads < devices).
    impl : "flash" (Pallas kernel), "einsum", or "auto".
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ulysses_attention needs a Mesh "
                         "(parallel.make_mesh)")
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            "ulysses_attention: num_heads=%d not divisible by mesh axis "
            "%r size %d (use ring_attention for few-head models)"
            % (q.shape[1], axis, n))
    if q.shape[2] % n:
        raise ValueError("ulysses_attention: seq=%d not divisible by "
                         "mesh axis %r size %d" % (q.shape[2], axis, n))
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = not on_tpu(q)
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis,
                          causal=bool(causal), sm_scale=float(sm_scale),
                          impl=impl, interpret=bool(interpret)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ulysses_self_attention(x, w_qkv, w_out, num_heads, mesh=None,
                           axis="sp", causal=False, impl="auto"):
    """Fused all-to-all sequence-parallel self-attention: x (b, seq, dm).

    Projections run on sequence-sharded activations (local matmuls);
    only the two all-to-alls move data between devices — the drop-in
    alternative to :func:`ring_self_attention`.
    """
    b, s, dm = x.shape
    qkv = jnp.einsum("bsd,de->bse", x, w_qkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, num_heads, dm // num_heads).transpose(
            0, 2, 1, 3)

    o = ulysses_attention(heads(q), heads(k), heads(v), mesh=mesh,
                          axis=axis, causal=causal, impl=impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, dm)
    return jnp.einsum("bsd,de->bse", o, w_out)
