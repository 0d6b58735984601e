"""Differential attention (arXiv:2410.05258) over paged K/V pairs: the
decode walk and the prefill of ``flash_attention.py``, on the layout a
differential model's cache keeps.

A differential head i has two softmax maps over one value: ``A1`` of
query head 2i against K head 2j, ``A2`` of 2i + 1 against 2j + 1, and
the value ``[v_2j | v_2j+1]``, twice a head wide. The cache keeps each
K/V head PAIR side by side, ``[k_2j | k_2j+1]`` — 128 lanes for heads of
64 — and the caller gives each head's two queries as two rows,
``[q_2i | 0]`` and ``[0 | q_2i+1]`` (``transformer._diff_queries``):
against a pair's key a row scores exactly its own map, and its output is
that map over the pair's whole value. So ONE grouped attention over the
pairs — a pair a "K/V head" of 2 hd, 2 g rows a pair — reads every live
page of a pair once and gives both maps of every head; the zero halves
cost products on the MXU, not bytes.

A pair count that does not fill a bf16 memory tile's rows (10 pairs:
Mosaic slices an HBM ref by whole tiles) cannot be copied by page out of
a ``(pages, page_size, pairs, 2 hd)`` pool, so the pool's pages are FLAT:
``(layers, pages, page_size * pairs, 2 hd)``, a page's (token, pair)
rows in the order the decode kernel's flat product takes them. The
decode kernel is ``flash_attention._paged_kernel`` on such pages; the
prefill is ``flash_attention._prefill_kernel`` without its page write
(the caller's XLA scatter writes the pages).

Forward-only (serving). Off the TPU the ``jax.numpy`` twins run (the
tier-1 path); ``interpret=True`` forces the Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_LANES, _PREFILL_VMEM_BUDGET, _out_vma,
                              _paged_decode_xla, _paged_kernel,
                              _pages_per_block, _prefill_kernel, on_tpu)

__all__ = ["diff_paged_decode", "diff_flash_prefill"]

PALLAS_KERNELS = {"diff_paged_decode": "_diff_paged_decode_xla",
                  "diff_flash_prefill": "_diff_prefill_xla"}


def _unflat(pages, pairs):
    """A flat pool as ``flash_attention``'s twin takes pools: (layers,
    pages, page_size, pairs, 2 hd) — a free reshape under XLA."""
    n_l, n_p, rows, w = pages.shape
    return pages.reshape(n_l, n_p, rows // pairs, pairs, w)


def _diff_paged_decode_xla(q, k_pages, v_pages, block_tables, lengths,
                           sm_scale, window=None, layer=0):
    """``jax.numpy`` twin of :func:`diff_paged_decode` (the CPU tier-1
    path): ``flash_attention``'s gather twin over the pages unflattened."""
    pairs = q.shape[1]
    return _paged_decode_xla(q, _unflat(k_pages, pairs),
                             _unflat(v_pages, pairs), block_tables, lengths,
                             sm_scale, window, layer)


def diff_paged_decode(q, k_pages, v_pages, block_tables, lengths,
                      page_size, sm_scale, window=None, layer=0,
                      interpret=None):
    """One query token a row against layer ``layer`` of a flat pool of
    K/V pairs.

    Parameters
    ----------
    q : (b, pairs, rows a pair, 2 hd) — the maps' query rows
        (``transformer._diff_queries``).
    k_pages, v_pages : (layers, num_pages, page_size * pairs, 2 hd) —
        the WHOLE pool of a kind of layer; never slice it by layer.
    block_tables : (b, entries) int32; lengths : (b,) int32 — row ``r``
        attends positions ``< lengths[r]``.
    window : optional int — the last ``window`` positions only, the
        table read as a ring (as ``paged_decode_attention``).
    layer : int — a scalar-prefetched operand: a model's layers share
        one lowered kernel.

    Returns (b, pairs, rows a pair, 2 hd): each row's map over its pair's
    whole value."""
    b, pairs, g, w = q.shape
    if k_pages.shape[2] != page_size * pairs:
        raise ValueError("a flat pool of %d rows a page is not %d tokens "
                         "of %d pairs" % (k_pages.shape[2], page_size,
                                          pairs))
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if interpret is None and not on_tpu(q):
        return _diff_paged_decode_xla(q, k_pages, v_pages, block_tables,
                                      lengths, float(sm_scale), window,
                                      layer)
    return _diff_paged_decode(
        q, k_pages, v_pages, block_tables, lengths,
        jnp.asarray([layer], jnp.int32), float(sm_scale), bool(interpret),
        None if window is None else int(window))


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "window"))
def _diff_paged_decode(q, k_pages, v_pages, block_tables, lengths, layer,
                       sm_scale, interpret, window):
    """The jitted wrapper's NAME is what the device trace prints for the
    custom call (``_diff_paged_decode.N``) and what the benchmark's kernel
    metrics look for: keep it."""
    b, pairs, g, w = q.shape
    rows = k_pages.shape[2]
    n_p = _pages_per_block(rows // pairs, pairs, w, k_pages.dtype.itemsize,
                           block_tables.shape[1])
    qo_spec = pl.BlockSpec((b, pairs * g, w), lambda *_: (0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    buf = pltpu.VMEM((2, n_p, rows, w), k_pages.dtype)
    return pl.pallas_call(
        functools.partial(_paged_kernel, sm_scale=sm_scale, window=window,
                          kv_heads=pairs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[qo_spec, hbm_spec, hbm_spec], out_specs=qo_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(
            (b, pairs * g, w), q.dtype, vma=_out_vma(q, k_pages, v_pages)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, layer, q.reshape(b, pairs * g, w), k_pages,
      v_pages).reshape(q.shape)


def _diff_prefill_xla(q, kp, vp, sm_scale, window):
    """``jax.numpy`` twin of :func:`diff_flash_prefill`."""
    from .flash_attention import causal_mask
    s, nh = q.shape[1:3]
    g = nh // kp.shape[2]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(kp, g, 2)) * sm_scale
    sc = jnp.where(causal_mask(s, window)[None, None], sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                      jnp.repeat(vp, g, 2)).astype(jnp.float32)


def diff_flash_prefill(q, kp, vp, sm_scale, window=None, block_q=128,
                       block_k=128, interpret=None):
    """Causal attention of whole prompts over their K/V pairs: q (b, s,
    rows, 2 hd) — the maps' query rows, pair-major — and kp, vp (b, s,
    pairs, 2 hd); position i attends ``0 <= i - j`` (``< window`` where
    given). Returns (b, s, rows, 2 hd) float32 (a head's two maps are
    subtracted: their reads keep their bits). Nothing is written: the
    caller scatters the pages."""
    if interpret is None:
        if not on_tpu(q) or q.shape[-1] % _LANES:
            return _diff_prefill_xla(q, kp, vp, sm_scale, window)
        interpret = False
    b, s, nh, w = q.shape
    block_k = min(block_k, s)
    while s % block_k:
        block_k //= 2
    block_q = min(block_q, s)
    row_bytes = nh * (2 * w * (q.dtype.itemsize + 4)
                      + 4 * (2 * _LANES + w))
    while s % block_q or (block_q > 8
                          and block_q * row_bytes > _PREFILL_VMEM_BUDGET):
        block_q //= 2
    return _diff_flash_prefill(q, kp, vp, float(sm_scale), window,
                               int(block_q), int(block_k), bool(interpret))


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "window", "block_q", "block_k", "interpret"))
def _diff_flash_prefill(q, kp, vp, sm_scale, window, block_q, block_k,
                        interpret):
    """The jitted wrapper's NAME is what the device trace prints for the
    custom call (``_diff_flash_prefill.N``): keep it."""
    b, s, nh, w = q.shape
    pairs = kp.shape[2]
    q_spec = pl.BlockSpec((1, block_q, nh, w),
                          lambda b_i, qi, ki, *_: (b_i, qi, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, pairs, w),
                           lambda b_i, qi, ki, *_: (b_i, ki, 0, 0))
    kernel = functools.partial(
        _prefill_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, page_size=block_k, seq_len=s, n_heads=nh,
        groups=nh // pairs, write_pages=False, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the kernel's table and layer operands: no page is written
            num_scalar_prefetch=2, grid=(b, s // block_q, s // block_k),
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((nh, block_q, _LANES), jnp.float32),
                            pltpu.VMEM((nh, block_q, _LANES), jnp.float32),
                            pltpu.VMEM((nh, block_q, w), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, s, nh, w), jnp.float32,
                                       vma=_out_vma(q, kp, vp)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.zeros((b, 1), jnp.int32), jnp.zeros((1,), jnp.int32), q, kp, vp)
