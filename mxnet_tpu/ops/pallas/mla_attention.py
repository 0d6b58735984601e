"""Latent attention (MLA, arXiv:2405.04434 sec. 2.1) as Pallas TPU kernels.

A latent-attention layer caches ONE vector a token — the normalised
compressed KV ``c_KV`` (``kv_rank`` wide) followed by the rotated key all
heads share (``rope`` wide) — and nothing per head. Two kernels attend over
it, and they compute the same mathematics:

* :func:`mla_paged_decode` — the ABSORBED form, one query token a row
  against a paged pool of latents: the key map is folded into the query
  (``q_lat = q_nope W_UK^T``), every head scores ``[q_lat; q_rope]`` against
  the cached vector itself, and the values are the first ``kv_rank``
  columns of the very block the keys were read from (read once). All heads
  share the one latent "KV head": ``heads x 2 x (width + kv_rank)``
  operations a cached vector of ``width x itemsize`` bytes — 242 FLOP/B at
  128 heads of 576 / 512 in bf16, the v5e's ridge — so the kernel feeds the
  MXU bf16 and keeps float32 only in the softmax and the accumulator. The
  layer index is a scalar-prefetched operand on the WHOLE pool
  (``flash_attention._paged_kernel`` says why: a ``pool[layer]`` in front
  of a custom call is a copy).
* :func:`mla_flash_prefill` — the DECOMPRESSED form over a whole prompt:
  per-head keys of ``nope + rope`` against values of ``v`` wide, causal,
  flash style. The rotated key is one for all heads and is never
  broadcast: a score is ``q_nope . k_nope + q_rope . k_rope``, two
  products into one tile.

Forward-only (serving); no VJP is defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _LANES, on_tpu

__all__ = ["mla_paged_decode", "mla_flash_prefill", "latent_pool_shape",
           "pages_of_latents", "lane_tile"]

PALLAS_KERNELS = {
    "mla_paged_decode": "_mla_paged_decode_xla",
    "mla_flash_prefill": "_mla_flash_prefill_xla",
}


# ---------------------------------------------------------------------------
# decode: one query a row over a paged pool of latents
# ---------------------------------------------------------------------------
#
# THE POOL'S LAYOUT. A page holds its latents TRANSPOSED, in lane tiles:
# (layers, num_pages, page_size / lt, width, lt) with ``lt = min(page_size,
# 128)`` — positions along the lanes, the latent's ``width`` values along
# the sublanes. A (positions, width) page is not what the chip would hold
# anyway: width = 576 is no multiple of 128 lanes, and for such an array
# the TPU's default layout puts the positions minor (so that nothing is
# padded to 640). A kernel operand must be row-major, so XLA re-laid the
# WHOLE pool out before the first kernel call of a step and back after the
# last (two copies of 2.6 GB a token step; tools/check_pool_in_place.py
# holds the repair). In this shape the logical and the physical order
# agree, every (width, lt) tile is whole (576 = 36 x 16 packed sublanes),
# a page is one contiguous block, and every index a write needs — layer,
# page, tile — is an untiled dim. Scores are ``q (heads, width) @ tile
# (width, lt)``, a plain product; the values are the tile's first
# ``kv_rank`` rows, contracted over the lanes.

def lane_tile(page_size):
    """Positions in one lane tile of a latent page."""
    lt = min(int(page_size), _LANES)
    if page_size % lt:
        raise ValueError("a latent page of %d positions is not whole lane "
                         "tiles of %d" % (page_size, lt))
    return lt


def latent_pool_shape(n_layers, num_pages, page_size, width):
    """Shape of a latent pool (the layout above)."""
    lt = lane_tile(page_size)
    return (int(n_layers), int(num_pages), int(page_size) // lt, int(width),
            lt)


def pages_of_latents(latent, page_size):
    """latent (b, s, width), ``s`` whole pages -> (b, s / page_size,
    page_size / lt, width, lt): the prompt's pages as the pool holds
    them."""
    b, s, width = latent.shape
    lt = lane_tile(page_size)
    return latent.reshape(b, s // page_size, page_size // lt, lt,
                          width).swapaxes(-1, -2)


def _mla_paged_decode_xla(q, pages, block_tables, lengths, sm_scale,
                          kv_rank, layer=None):
    """Pure-lax twin (the CPU tier-1 path): gather each row's latents by
    its block table, score every head against them, weigh their first
    ``kv_rank`` values."""
    if layer is not None:
        pages = pages[layer]
    kc = pages[block_tables]                  # (b, entries, T, width, lt)
    b, n_e, n_t, width, lt = kc.shape
    kc = kc.swapaxes(-1, -2).reshape(b, n_e * n_t * lt, width).astype(
        jnp.float32)
    sc = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), kc) * sm_scale
    visible = jnp.arange(kc.shape[1])[None, :] < lengths[:, None]
    sc = jnp.where(visible[:, None, :], sc, NEG_INF)
    o = jnp.einsum("bhl,blr->bhr", jax.nn.softmax(sc, -1),
                   kc[..., :kv_rank])
    return o.astype(q.dtype)


def _mla_decode_kernel(bt_ref, len_ref, _layer_ref, q_ref, kv_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, page_size, sm_scale,
                       kv_rank):
    """Grid (b, entries): the trailing dimension walks a row's block
    table and accumulates an online softmax over every head at once —
    ``q_ref`` (heads, width) against one page ``kv_ref`` (tiles, width,
    lt) of one layer of the whole pool. Entries past the row's length
    keep the last live page's block index (no fetch) and skip the body."""
    b_i = pl.program_id(0)
    p_i = pl.program_id(1)
    n_p = pl.num_programs(1)
    n_t, _width, lt = kv_ref.shape

    @pl.when(p_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b_i]
    start = p_i * page_size

    @pl.when(start < length)
    def _body():
        q = q_ref[0]                                          # (H, width)
        s = jnp.concatenate(
            [jnp.dot(q, kv_ref[t], preferred_element_type=jnp.float32)
             for t in range(n_t)], axis=1) * sm_scale         # (H, ps)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, -1, keepdims=True)
        # the values ARE the tiles' first kv_rank rows, contracted over
        # the lanes
        p = p.astype(kv_ref.dtype)
        pv = sum(jax.lax.dot_general(
            p[:, t * lt:(t + 1) * lt], kv_ref[t, :kv_rank, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for t in range(n_t))                              # (H, rank)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p_i == n_p - 1)
    def _fin():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "kv_rank",
                                             "interpret"))
def _mla_paged_decode(q, pages, block_tables, lengths, layer, sm_scale,
                      kv_rank, interpret):
    b, n_heads, width = q.shape
    n_t, _, lt = pages.shape[2:]
    page_size = n_t * lt
    n_pb = block_tables.shape[1]

    def q_map(b_i, p_i, bt, ln, li):
        return (b_i, 0, 0)

    def kv_map(b_i, p_i, bt, ln, li):
        last = jnp.maximum(ln[b_i] - 1, 0) // page_size
        return (li[0], bt[b_i, jnp.minimum(p_i, last)], 0, 0, 0)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pb),
        in_specs=[pl.BlockSpec((1, n_heads, width), q_map),
                  pl.BlockSpec((None, None, n_t, width, lt), kv_map)],
        out_specs=pl.BlockSpec((1, n_heads, kv_rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, kv_rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, page_size=page_size,
                          sm_scale=sm_scale, kv_rank=kv_rank),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, layer, q, pages)


def mla_paged_decode(q, pages, block_tables, lengths, sm_scale, kv_rank,
                     interpret=None, layer=None):
    """Absorbed latent attention for one query token a row.

    Parameters
    ----------
    q : (b, heads, width) — per head ``[q_nope W_UK^T ; q_rope]``, width =
        ``kv_rank + rope``.
    pages : (layers, num_pages, page_size / lt, width, lt) — the WHOLE
        latent pool (:func:`latent_pool_shape`) with ``layer``; or one
        layer's 4-D pool (a free reshape to a pool of one layer). Never
        slice the pool by layer for this call.
    block_tables : (b, entries) int32 — page ids in position order.
    lengths : (b,) int32 — row ``r`` attends positions ``< lengths[r]``.
    sm_scale : the score scale (``(nope + rope) ** -0.5``, times YaRN's
        ``mscale ** 2`` where the model has it).
    kv_rank : the leading values of a cached vector that are its values.

    Returns (b, heads, kv_rank): ``softmax(q . latent) latent[:kv_rank]``,
    which the caller maps through ``W_UV``. On TPU a Mosaic kernel whose
    page DMAs follow the scalar-prefetched block table; off-TPU the lax
    twin; ``interpret=True`` forces the Pallas interpreter."""
    if pages.ndim == 4:
        if layer is not None:
            raise ValueError("layer=%r given with one layer's 4-D pool"
                             % (layer,))
    elif layer is None or not 0 <= layer < pages.shape[0]:
        raise ValueError("a whole pool %s needs its layer's index, got %r"
                         % (pages.shape, layer))
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if interpret is None:
        if not on_tpu(q):
            return _mla_paged_decode_xla(q, pages, block_tables, lengths,
                                         float(sm_scale), int(kv_rank),
                                         layer)
        interpret = False
    if layer is None:
        pages, layer = pages[None], 0
    return _mla_paged_decode(q, pages, block_tables, lengths,
                             jnp.asarray([layer], jnp.int32),
                             float(sm_scale), int(kv_rank), bool(interpret))


# ---------------------------------------------------------------------------
# prefill: decompressed per-head keys and values over a whole prompt
# ---------------------------------------------------------------------------

def _mla_flash_prefill_xla(q_nope, q_rope, k_nope, k_rope, v, sm_scale):
    """Pure-lax twin: the (s, s) scores of every head, causal."""
    f32 = jnp.float32
    sc = (jnp.einsum("bhqd,bhkd->bhqk", q_nope.astype(f32),
                     k_nope.astype(f32))
          + jnp.einsum("bhqd,bkd->bhqk", q_rope.astype(f32),
                       k_rope.astype(f32))) * sm_scale
    s = sc.shape[-1]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                   NEG_INF)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1),
                   v.astype(f32))
    return o.astype(v.dtype)


def _mla_prefill_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, sm_scale, block_q,
                        block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # k blocks wholly above the diagonal are skipped (and not fetched:
    # their block index is held at the last one needed)
    @pl.when(k_start <= q_start + block_q - 1)
    def _body():
        contract = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[0], contract,
                                   preferred_element_type=jnp.float32)
             ) * sm_scale                                    # (bq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0]
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)     # (bq, dv)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_q",
                                             "block_k", "interpret"))
def _mla_flash_prefill(q_nope, q_rope, k_nope, k_rope, v, sm_scale, block_q,
                       block_k, interpret):
    b, n_heads, s, d_nope = q_nope.shape
    d_rope, d_v = q_rope.shape[-1], v.shape[-1]
    fold = lambda t: t.reshape((b * n_heads,) + t.shape[2:])

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def needed(qi, ki):
        return jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k)

    def k_map(bh, qi, ki):
        return (bh, needed(qi, ki), 0)

    def kr_map(bh, qi, ki):
        return (bh // n_heads, needed(qi, ki), 0)

    o = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        grid=(b * n_heads, s // block_q, s // block_k),
        in_specs=[pl.BlockSpec((1, block_q, d_nope), q_map),
                  pl.BlockSpec((1, block_q, d_rope), q_map),
                  pl.BlockSpec((1, block_k, d_nope), k_map),
                  pl.BlockSpec((1, block_k, d_rope), kr_map),
                  pl.BlockSpec((1, block_k, d_v), k_map)],
        out_specs=pl.BlockSpec((1, block_q, d_v), q_map),
        out_shape=jax.ShapeDtypeStruct((b * n_heads, s, d_v), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(fold(q_nope), fold(q_rope), fold(k_nope), k_rope, fold(v))
    return o.reshape(b, n_heads, s, d_v)


def mla_flash_prefill(q_nope, q_rope, k_nope, k_rope, v, sm_scale,
                      block_q=512, block_k=512, interpret=None):
    """Causal decompressed latent attention over a prompt.

    q_nope, k_nope : (b, heads, s, nope); q_rope : (b, heads, s, rope),
    rotated; k_rope : (b, s, rope) — the ONE rotated key of all heads;
    v : (b, heads, s, v). ``s`` is a multiple of the blocks (they are cut
    to it). Returns (b, heads, s, v): position ``i`` attends ``<= i`` with
    scores ``(q_nope . k_nope + q_rope . k_rope) * sm_scale``. On TPU a
    Mosaic kernel (bf16 into the MXU, float32 softmax); off-TPU the lax
    twin; ``interpret=True`` forces the Pallas interpreter."""
    s = q_nope.shape[2]
    if interpret is None:
        if not on_tpu(q_nope):
            return _mla_flash_prefill_xla(q_nope, q_rope, k_nope, k_rope, v,
                                          float(sm_scale))
        interpret = False
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError("sequence %d is not a multiple of the blocks "
                         "(%d, %d)" % (s, block_q, block_k))
    return _mla_flash_prefill(q_nope, q_rope, k_nope, k_rope, v,
                              float(sm_scale), int(block_q), int(block_k),
                              bool(interpret))
