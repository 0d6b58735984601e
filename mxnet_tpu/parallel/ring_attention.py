"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence parallelism (SURVEY.md §5 "long-context":
its long-sequence story is bucketing + fused RNNs). This is the
TPU-first, first-class replacement: Q/K/V are sharded along the
*sequence* dimension over a mesh axis; each device attends its local Q
block against K/V chunks that rotate around the ring via
``lax.ppermute`` over ICI, with an online-softmax accumulator so no
device ever materialises more than one remote chunk. Compute and
communication overlap naturally: XLA schedules the next permute
alongside the current block's matmuls.

Complexity per device: O(S_local * S * d) FLOPs, O(S_local * d) memory
— sequences scale linearly with the number of devices in the ring.

Differentiable end-to-end (ppermute has a transpose rule, the rest is
pure jnp), so it drops straight into sharded training steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..ops.pallas.flash_attention import on_tpu

NEG_INF = -1e30

__all__ = ["ring_attention", "ring_self_attention"]


def _chunk_attention(q, k, v, q_off, k_off, causal, scale):
    """One Q-block x one K/V-chunk step; returns (pv, m, l) in f32.

    q: (b, h, sq, d) local queries (pre-scaled), k/v: (b, h, sk, d).
    q_off / k_off: global sequence offsets of the blocks (traced ints).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[2])[:, None]
        kpos = k_off + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                   # (b,h,sq,1)
    # all-masked rows: keep exp() finite
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)                   # (b,h,sq,1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p,
                    v.astype(jnp.float32))                   # (b,h,sq,d)
    return pv, m_safe, l


def _ring_attention_local(q, k, v, axis_name, causal, sm_scale,
                          impl="auto", interpret=None):
    """Per-shard ring attention body (runs inside shard_map).

    impl="flash" streams each rotating K/V chunk through the Pallas
    flash-attention kernel (ops/pallas/flash_attention.py) and merges
    chunk outputs by log-sum-exp — O(block) VMEM instead of the
    O(S_local^2) score matrix; impl="einsum" is the plain-XLA reference
    path; "auto" picks flash (the kernel interprets itself off-TPU).
    """
    if impl == "auto":
        impl = "flash"
    if impl == "flash":
        if interpret is None:
            interpret = not on_tpu(q)
        return _ring_flash(q, k, v, axis_name, bool(causal),
                           float(sm_scale), bool(interpret))
    return _ring_einsum_local(q, k, v, axis_name, causal, sm_scale)


# ---------------------------------------------------------------------------
# flash-kernel ring path (forward: Pallas chunks + LSE merge; backward:
# blockwise recompute with the chunk gradients riding the ring home)
# ---------------------------------------------------------------------------

def _chunk_block_sizes(s_q, s_k):
    return min(128, max(8, s_q)), min(128, max(8, s_k))


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale, interpret):
    from ..ops.pallas.flash_attention import _flash_fwd
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    bq, bk = _chunk_block_sizes(s_local, s_local)
    perm = [(i, (i + 1) % n) for i in range(n)]

    out_acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    lse_acc = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    k_cur, v_cur = k, v
    for j in range(n):
        if j == 0:
            # diagonal chunk: local q and k offsets align, the kernel's
            # relative causal mask IS the global causal mask
            o_c, lse_c = _flash_fwd(q, k_cur, v_cur, causal, sm_scale,
                                    bq, bk, interpret)
        elif causal:
            # chunk owner src=(idx-j)%n is fully visible iff idx >= j,
            # fully hidden otherwise (never partially visible)
            o_c, lse_c = jax.lax.cond(
                idx >= j,
                lambda kc, vc: _flash_fwd(q, kc, vc, False, sm_scale,
                                          bq, bk, interpret),
                # NEG_INF lse derived from q so its varying-axes (vma)
                # match the kernel branch under any enclosing mesh axes
                lambda kc, vc: (jnp.zeros_like(q),
                                jnp.sum(jnp.zeros_like(q, dtype=jnp.float32),
                                        axis=-1) + NEG_INF),
                k_cur, v_cur)
        else:
            o_c, lse_c = _flash_fwd(q, k_cur, v_cur, False, sm_scale,
                                    bq, bk, interpret)
        lse_new = jnp.logaddexp(lse_acc, lse_c)
        w_prev = jnp.exp(lse_acc - lse_new)[..., None]
        w_cur = jnp.exp(lse_c - lse_new)[..., None]
        out_acc = out_acc * w_prev + o_c.astype(jnp.float32) * w_cur
        lse_acc = lse_new
        if j < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    return out_acc.astype(q.dtype), lse_acc


def _ring_flash_bwd_impl(axis_name, causal, sm_scale, interpret, res, g):
    """Blockwise backward: recompute probabilities per chunk from the
    saved global LSE (flash-attention-2 identity p = exp(s - lse)); dK/dV
    accumulate on a buffer that rotates WITH its chunk, so after n hops
    every chunk arrives home carrying its full gradient."""
    q, k, v, o, lse = res
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    g = g.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * g, axis=-1)        # (b,h,sq)
    qpos = idx * s_local + jnp.arange(s_local)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq = jnp.zeros((b, h, s_local, d), jnp.float32)
    k_cur, v_cur = k, v
    dk_cur = jnp.zeros((b, h, s_local, d), jnp.float32)
    dv_cur = jnp.zeros((b, h, s_local, d), jnp.float32)
    for j in range(n):
        src = (idx - j) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       k_cur.astype(jnp.float32)) * sm_scale
        if causal:
            kpos = src * s_local + jnp.arange(s_local)
            s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None],
                          s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                        # 0 when masked
        dv_cur = dv_cur + jnp.einsum("bhqk,bhqd->bhkd", p, g)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, v_cur.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * sm_scale
        dk_cur = dk_cur + jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_cur.astype(jnp.float32))
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
    return (dq.astype(q.dtype), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, sm_scale, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                  interpret)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, sm_scale, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                    interpret)
    return out, (q, k, v, out, lse)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_bwd_impl)


def _ring_einsum_local(q, k, v, axis_name, causal, sm_scale):
    """Plain-XLA per-shard body (the non-kernel reference path)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    qf = q.astype(jnp.float32)
    q_off = idx * s_local

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(j, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - j) % n                                   # chunk owner
        pv, m_c, l_c = _chunk_attention(
            qf, k_cur, v_cur, q_off, src * s_local, causal, sm_scale)
        m_new = jnp.maximum(m, m_c)
        a_prev = jnp.exp(m - m_new)
        a_cur = jnp.exp(m_c - m_new)
        acc = acc * a_prev + pv * a_cur
        l = l * a_prev + l_c * a_cur
        # rotate K/V one hop around the ring (ICI neighbour exchange)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l, acc

    b, h, _, d = q.shape
    m0 = jnp.full((b, h, s_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    carry = (k, v, m0, l0, acc0)
    # n is a Python int (mesh size is static) — unrolled scan keeps each
    # ppermute a distinct collective XLA can overlap with compute.
    for j in range(n):
        carry = step(j, carry)
    _, _, _, l, acc = carry
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False,
                   sm_scale=None, impl="auto"):
    """Sequence-parallel attention over mesh axis ``axis``.

    q, k, v : (batch, heads, seq, head_dim), with seq divisible by the
        axis size. Arrays may be unsharded (shard_map partitions them).
    mesh : jax.sharding.Mesh (defaults to parallel.current_mesh()).
    impl : "flash" (Pallas kernel per chunk), "einsum", or "auto".
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a Mesh (parallel.make_mesh)")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis,
                          causal=bool(causal), sm_scale=float(sm_scale),
                          impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_self_attention(x, w_qkv, w_out, num_heads, mesh=None, axis="sp",
                        causal=False):
    """Fused sequence-parallel self-attention block: x (batch, seq, dm).

    QKV/out projections run on the sequence-sharded activations (fully
    local matmuls); only the ring exchange moves data between devices.
    """
    b, s, dm = x.shape
    qkv = jnp.einsum("bsd,de->bse", x, w_qkv)                 # (b,s,3dm)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, num_heads, dm // num_heads).transpose(
            0, 2, 1, 3)

    o = ring_attention(heads(q), heads(k), heads(v), mesh=mesh, axis=axis,
                       causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, dm)
    return jnp.einsum("bsd,de->bse", o, w_out)
