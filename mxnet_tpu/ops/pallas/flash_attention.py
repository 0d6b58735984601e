"""Flash attention as a Pallas TPU kernel.

Capability analog of the reference's fused transformer attention ops
(reference: src/operator/contrib/transformer-inl.h) redesigned for TPU:
instead of materialising the (S, S) score matrix in HBM, the kernel
streams K/V blocks through VMEM with an online-softmax accumulator, so
memory is O(S * d) and the matmuls stay on the MXU.

Forward  = Pallas kernel over grid (batch*heads, q_blocks, k_blocks);
           scratch accumulators (m, l, acc) persist across the k grid
           dimension (TPU grids iterate the trailing dim sequentially).
Backward = blockwise lax.scan recomputation from the saved per-row
           log-sum-exp (flash-attention-2 style: p = exp(qk - lse)),
           memory O(block * S), fully fused by XLA.

Layout: (batch, heads, seq, head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "paged_decode_attention",
           "flash_prefill_paged", "on_tpu"]

# kernel-contract registry: every exported Pallas kernel maps to its
# module-level pure-lax twin (tools/check_pallas_contracts.py fails the
# suite if an exported kernel is missing here, its twin touches
# pallas_call, or tests/ lacks an interpret-mode parity test)
PALLAS_KERNELS = {
    "flash_attention": "_flash_fwd_xla",
    "paged_decode_attention": "_paged_decode_xla",
    "flash_prefill_paged": "_flash_prefill_xla",
}

NEG_INF = -1e30
_LANES = 128


def on_tpu(x=None):
    """Whether the program consuming ``x`` is compiled for a TPU — the
    one test every kernel entry point and caller branch uses to pick
    the Mosaic kernel over interpret mode or a lax twin.

    A concrete array answers with its own devices: jit follows
    committed inputs, so a cpu(0)-context NDArray on a TPU machine runs
    on the host. A tracer (or no array) has no devices; the enclosing
    program is lowered for the default backend, and should its inputs
    turn out to be committed to the host, the Mosaic lowering raises
    ("Only interpret mode is supported on CPU backend") — on a TPU
    machine a traced call can therefore never land in a twin."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return all(d.platform == "tpu" for d in x.devices())
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q, block_k,
                seq_len):
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

    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                     # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)

        # mask out-of-range keys (padding) and the causal triangle
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
        p = jnp.exp(s - m_new)                               # (bq, bk)

        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                     # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, d)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip K blocks entirely above the causal diagonal
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = (m_scr[:, :1] + jnp.log(l_safe))               # (bq, 1)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:]).astype(
            lse_ref.dtype)


def _pad_to(x, mult, axis):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def _out_vma(*xs):
    """Union of the inputs' varying-across-mesh axes, so pallas_call
    outputs carry the right `vma` under shard_map(check_vma=True)."""
    vma = frozenset()
    for x in xs:
        vma |= jax.typeof(x).vma
    return vma


def _flash_fwd_xla(q, k, v, causal, sm_scale):
    """Plain-XLA twin of the kernel (same (o, lse) contract).

    Used when the kernel would run under the Pallas *interpreter* inside
    a shard_map manual context: the interpreter's internal dynamic_slice
    ops trip check_vma there (JAX-internal limitation). Off the manual
    path the interpreter still exercises the real kernel logic.
    ``interpret`` is True only off-TPU (:func:`on_tpu`) or at the
    caller's explicit request, so on TPU the compiled Mosaic kernel
    always runs.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qpos = jnp.arange(q.shape[2])[:, None]
        kpos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / l_safe, v.astype(jnp.float32))
    lse = (m + jnp.log(l_safe))[..., 0]
    return o.astype(q.dtype), lse


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k", "interpret"))
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    if interpret and _out_vma(q, k, v):
        return _flash_fwd_xla(q, k, v, causal, sm_scale)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)

    qf = _pad_to(qf, block_q, 1)
    kf = _pad_to(kf, block_k, 1)
    vf = _pad_to(vf, block_k, 1)
    sp_q, sp_k = qf.shape[1], kf.shape[1]
    grid = (b * h, sp_q // block_q, sp_k // block_k)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=s_k)
    vma = _out_vma(q, k, v)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sp_q, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, sp_q, _LANES), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)

    o = o[:, :s_q].reshape(b, h, s_q, d)
    lse = lse[:, :s_q, 0].reshape(b, h, s_q)
    return o, lse


# ---------------------------------------------------------------------------
# backward: blockwise recomputation from saved lse (XLA, scan over k blocks)
# ---------------------------------------------------------------------------

def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    del block_q, interpret
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    g = g.astype(jnp.float32)
    qf = q.astype(jnp.float32) * sm_scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # delta_i = sum_d o_i * do_i  (rowwise), standard flash-bwd shortcut
    delta = jnp.sum(o.astype(jnp.float32) * g, axis=-1)          # (b,h,sq)

    nk = max(1, -(-s_k // block_k))
    pad_k = nk * block_k - s_k
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    kpos = jnp.arange(nk * block_k)
    qpos = jnp.arange(s_q)

    def kblock(carry, kb):
        dq_acc = carry
        ks = kb * block_k
        kblk = jax.lax.dynamic_slice_in_dim(kf, ks, block_k, axis=2)
        vblk = jax.lax.dynamic_slice_in_dim(vf, ks, block_k, axis=2)
        kp = jax.lax.dynamic_slice_in_dim(kpos, ks, block_k)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk)              # (b,h,sq,bk)
        mask = (kp[None, None, None, :] < s_k)
        if causal:
            mask = jnp.logical_and(
                mask, kp[None, None, None, :] <= qpos[None, None, :, None])
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                          # (b,h,sq,bk)
        p = jnp.where(mask, p, 0.0)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, g)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, vblk)
        ds = p * (dp - delta[..., None])                         # (b,h,sq,bk)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)               # scaled q
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk)
        return dq_acc, (dk, dv)

    # init carry derives from qf so its varying-across-mesh axes match
    # the body output under an enclosing shard_map (scan rejects a
    # non-varying init against a varying carry)
    dq, (dks, dvs) = jax.lax.scan(kblock, qf * 0.0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, nk * block_k, d)[:, :, :s_k]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, nk * block_k, d)[:, :, :s_k]
    dq = dq * sm_scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_vjp_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=128, block_k=128, interpret=None):
    """Memory-efficient attention: ``softmax(Q K^T * scale [+ mask]) V``.

    Parameters
    ----------
    q, k, v : arrays of shape (batch, heads, seq, head_dim).
    causal : apply a lower-triangular mask.
    sm_scale : score scale; default ``1/sqrt(head_dim)``.
    block_q, block_k : VMEM tile sizes (multiples of 128 on TPU).
    interpret : force pallas interpreter mode (defaults to True off-TPU).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = not on_tpu(q)
    block_q = min(block_q, max(8, q.shape[2]))
    block_k = min(block_k, max(8, k.shape[2]))
    return _flash(q, k, v, bool(causal), float(sm_scale),
                  int(block_q), int(block_k), bool(interpret))


# ---------------------------------------------------------------------------
# paged decode attention (serving: one query token per sequence against
# a block-table-addressed page pool — serve/decode.py's hot kernel)
# ---------------------------------------------------------------------------

def ring_positions(n_entries, page_size, lengths):
    """Absolute position held by every slot of a RING block table:
    entry ``e`` of a row whose newest position is ``lengths - 1`` holds
    the newest page ``a`` with ``a % n_entries == e`` and ``a <=
    (lengths - 1) // page_size`` (negative: never written). Returns
    (b, n_entries * page_size) int32. While a row has not wrapped this
    is ``arange``, so a table as long as the context is a ring too."""
    a_last = (lengths - 1) // page_size                        # (b,)
    e = jnp.arange(n_entries, dtype=jnp.int32)[None, :]
    a = a_last[:, None] - (a_last[:, None] - e) % n_entries    # (b, R)
    pos = a[:, :, None] * page_size \
        + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    return pos.reshape(lengths.shape[0], n_entries * page_size)


def _paged_decode_xla(q, k_pages, v_pages, block_tables, lengths,
                      sm_scale, window=None, layer=None):
    """Pure-lax twin of the paged kernel (the CPU tier-1 path and the
    numeric reference): block-table gather materializes each row's
    (L, kv_heads, hd) view, then standard masked GQA softmax. With a
    ``window`` the table is a ring (:func:`ring_positions`) and only
    the last ``window`` positions are visible. With a ``layer`` the
    pools are whole, (layers, ...), and that layer's pages are read."""
    b, kvh, g, hd = q.shape
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    kc = k_pages[block_tables]           # (b, pages, page_size, kvh, hd)
    vc = v_pages[block_tables]
    L = kc.shape[1] * kc.shape[2]
    kc = kc.reshape(b, L, kvh, hd).transpose(0, 2, 1, 3)
    vc = vc.reshape(b, L, kvh, hd).transpose(0, 2, 1, 3)
    if window is None:
        visible = jnp.arange(L)[None, :] < lengths[:, None]   # (b, L)
    else:
        kpos = ring_positions(block_tables.shape[1], k_pages.shape[1],
                              lengths)
        visible = jnp.logical_and(
            jnp.logical_and(kpos >= 0, kpos < lengths[:, None]),
            kpos >= lengths[:, None] - window)
    sc = jnp.einsum("bkgd,bkld->bkgl", q.astype(jnp.float32),
                    kc.astype(jnp.float32)) * sm_scale
    sc = jnp.where(visible[:, None, None, :], sc, NEG_INF)
    o = jnp.einsum("bkgl,bkld->bkgd", jax.nn.softmax(sc, -1),
                   vc.astype(jnp.float32))
    return o.astype(q.dtype)


# VMEM the walk's page blocks may claim: two slots each of K and V, an
# eighth of a v5e core's 16 MiB scoped limit (the rest is q, o, the f32
# working set of a block — its scores against every head — and the
# compiler's own stack)
_PAGED_VMEM_BUDGET = 2 << 20
# ... and the most tokens one compute block holds, where a page is
# smaller: longer blocks ran no faster on the chip, and what a row's last
# block holds past the row's length is worked on for nothing
_PAGED_BLOCK_TOKENS = 256


def _pages_per_block(page_size, kv_heads, head_dim, itemsize, n_entries):
    """``P``, the pages one compute block of the decode walk holds: as
    many as give a block of up to ``_PAGED_BLOCK_TOKENS`` tokens whose
    four buffers (K and V, double-buffered) fit ``_PAGED_VMEM_BUDGET``,
    by the page's bytes: 8 pages of 16 tokens x 16 heads x 128 in bf16
    (64 KB), 16 of 16 x 4 x 128 (16 KB), one of 512 x 2 x 256 (512 KB);
    never more than the table has entries."""
    page_bytes = page_size * kv_heads * head_dim * itemsize
    return max(1, min(_PAGED_VMEM_BUDGET // (4 * page_bytes),
                      _PAGED_BLOCK_TOKENS // page_size, n_entries))


def _pages_can_be_copied(kv_heads, head_dim, itemsize):
    """Whether Mosaic can slice one page out of a pool in HBM, which the
    decode walk's copies do: an HBM ref is sliced by whole memory tiles
    of its two minor dims, so ``head_dim`` must fill 128-lane tiles and,
    in a packed dtype, ``kv_heads`` the tile's rows (2, 4 or 8 of them:
    the least power of two that holds the heads and a packed pair).
    float32 pools take any head count. No served model falls outside;
    a pool that does is read by the twin (``tools/check_mosaic_aot.py``
    holds both sides of the rule against the compiler)."""
    if head_dim % _LANES:
        return False
    if itemsize >= 4:
        return True
    rows = 4 // itemsize
    while rows < min(kv_heads, 8):
        rows *= 2
    return kv_heads % rows == 0


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, *, sm_scale, window=None):
    """ONE invocation walks every row's LIVE pages, ``P`` pages a compute
    block (``kbuf``/``vbuf`` are ``(2, P, page_size, kv_heads, hd)``: two
    slots), accumulating an online softmax in float32 — running maximum,
    sum and accumulator are the block loop's carry.

    The walk. Row ``r`` of length ``n`` has written logical pages ``0 ..
    (n - 1) // page_size``; under a ``window`` the table is a ring
    (:func:`ring_positions`: page ``a`` at entry ``a % entries``) and
    only the pages the ring still holds that reach into the last
    ``window`` positions count. Those are the row's live pages, walked in
    position order, ``cdiv(live, P)`` blocks a row: a dummy slot (length
    1) costs one page, not a table's worth of grid steps over the null
    page. The kernel issues its own page copies from the scalar-
    prefetched block table — one DMA a live page, K and V each, into the
    slot the arithmetic is not reading — and the first block of row ``r
    + 1`` is in flight while row ``r`` finishes. A length under 1 reads
    as 1 (every row owns a block, so the chain of copies never breaks).

    The arithmetic. A block is two products for all its heads
    (``q_ref``/``o_ref`` are ``(rows, kv_heads * g, hd)``): its
    ``(tokens, kv_heads)`` rows flat against every query head, the scores
    of another K/V head's rows masked out like the positions past the
    length. Mosaic cannot take one head of a page without a strided copy
    of it, and one-row products a head leave the MXU idle: even at a
    group of one query head a K/V head, where fifteen sixteenths of the
    scores are masked, the flat product runs at the speed of the copies.
    Scores, maximum, sum and accumulator are float32, and the products
    take float32 operands at the MXU's default precision, as the
    grid-per-page kernel before this one did.

    Nothing is done to the pool outside the kernel: it stays whole in
    HBM, ``(layers, pages, page_size, kv_heads, hd)``, and the layer's
    index is a third scalar-prefetched operand, so every layer of a
    model runs ONE lowered kernel (a static index would be lowered once
    a layer, 24 times the set-up). XLA has no view of an array for a
    custom call's operand, so a reshape is a relayout copy of the WHOLE
    pool per call and a ``pool[layer]`` slice a copy of that layer's
    share of it (201 MB a call at the served size: the whole pool once a
    token step)."""
    n_rows, n_heads, hd = q_ref.shape
    _, n_p, page_size, kvh = kbuf.shape[:4]
    g = n_heads // kvh
    layer = layer_ref[0]
    n_entries = bt_ref.shape[1]

    def live_pages(r):
        """(length, first live logical page, how many) of row ``r``."""
        length = jnp.maximum(len_ref[r], 1)
        a_last = jax.lax.div(length - 1, page_size)
        if window is None:
            return length, 0, jnp.minimum(a_last + 1, n_entries)
        a_first = jnp.maximum(
            jax.lax.div(jnp.maximum(length - window, 0), page_size),
            a_last - n_entries + 1)
        return length, a_first, a_last - a_first + 1

    def block_extent(r, i):
        """(table entry of the first page, live pages) of block ``i`` of
        row ``r``."""
        _, a_first, n_live = live_pages(r)
        e_0 = a_first + i * n_p
        if window is not None:
            e_0 = jax.lax.rem(e_0, n_entries)
        return e_0, jnp.minimum(n_p, n_live - i * n_p)

    def page_copies(page, slot, j):
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, j], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, j], sem.at[1, slot]))

    def start_block(r, i, slot):
        e_0, n = block_extent(r, i)

        def one(j, carry):
            entry = e_0 + j
            if window is not None:      # the ring wraps inside a block
                entry = jnp.where(entry >= n_entries, entry - n_entries,
                                  entry)
            for copy in page_copies(bt_ref[r, entry], slot, j):
                copy.start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def wait_block(r, i, slot):
        def one(j, carry):
            # (a wait reads its descriptor's size and semaphore only)
            for copy in page_copies(0, slot, j):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, block_extent(r, i)[1], one, 0)

    def attend_block(q, slot, pos_0, length, carry):
        """One block, its (token, K/V head) rows flat against every
        query head. ``q`` (kv_heads * g, hd), scaled."""
        m, l, acc = carry
        n_cols = n_p * page_size * kvh
        k = kbuf[slot].reshape(n_cols, hd)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (heads, cols)
        # column c is position pos_0 + c // kvh of K/V head c % kvh;
        # query head h reads K/V head h // g
        col = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (n_heads, 1), 0)
        seen = col < (length - pos_0) * kvh
        if window is not None:
            seen = jnp.logical_and(
                seen, col >= (length - window - pos_0) * kvh)
        seen = jnp.logical_and(
            seen, jax.lax.rem(col, kvh) == jax.lax.div(row, g))
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)          # 0 where masked: m_new is finite
        v = vbuf[slot].reshape(n_cols, hd)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (heads, hd)
        return (m_new, alpha * l + jnp.sum(p, -1, keepdims=True),
                alpha * acc + pv)

    # a row's last block may be partly copied: what is left in the slot
    # there is masked out of the scores, but p = 0 times a NaN is a NaN,
    # so the V slots start finite (what an earlier block left is a live
    # page's, finite by the same argument)
    vbuf[...] = jnp.zeros_like(vbuf)
    start_block(0, 0, 0)

    def row(r, slot):
        length, a_first, n_live = live_pages(r)
        n_blocks = jax.lax.div(n_live + n_p - 1, n_p)
        q = q_ref[r].astype(jnp.float32) * sm_scale

        def block(i, carry):
            slot, carry = carry[0], carry[1:]
            last = i + 1 == n_blocks
            nxt = jnp.where(last, r + 1, r)

            @pl.when(nxt < n_rows)
            def _prefetch():
                start_block(nxt, jnp.where(last, 0, i + 1), 1 - slot)

            wait_block(r, i, slot)
            # (a block's first page is live and a live page always holds
            # a visible key of every head, so the running maximum is
            # finite from the first block on)
            return (1 - slot,) + attend_block(
                q, slot, (a_first + i * n_p) * page_size, length, carry)

        slot, _, l, acc = jax.lax.fori_loop(
            0, n_blocks, block,
            (slot, jnp.full((n_heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, hd), jnp.float32)))
        o_ref[r] = (acc / l).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, n_rows, row, 0)


def _check_layer(k_pages, layer):
    """A whole 5-D pool comes with the index of a layer it has; one
    layer's 4-D pool comes without."""
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("layer=%r given with one layer's 4-D pool"
                             % (layer,))
    elif layer is None or not 0 <= layer < k_pages.shape[0]:
        raise ValueError("a whole pool %s needs its layer's index, got %r"
                         % (k_pages.shape, layer))


def _whole_pools(k_pages, v_pages, layer):
    """``(k_pages, v_pages, layer)`` as the kernels take them, 5-D and a
    (1,) int32 index: one layer's 4-D pool is a whole pool of one layer."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return k_pages, v_pages, jnp.asarray([layer], jnp.int32)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           sm_scale=None, interpret=None, window=None,
                           layer=None):
    """Decode-phase attention against a PAGED KV cache: one query token
    per sequence, keys/values gathered page-by-page via a block table.

    Parameters
    ----------
    q : (b, kv_heads, group, head_dim) — query heads grouped per shared
        K/V head (GQA layout; ``group = n_heads // kv_heads``).
    k_pages, v_pages : (layers, num_pages, page_size, kv_heads,
        head_dim) — the WHOLE pool of a kind of layer, as the cache
        holds it — with ``layer``; or one layer's (num_pages, page_size,
        kv_heads, head_dim), which is the same thing with one layer (a
        free reshape). Never slice a pool by layer for this call: a
        slice in front of a custom call is a copy (``_paged_kernel``).
    block_tables : (b, pages_per_seq) int32 — page ids per row, in
        position order.
    lengths : (b,) int32 — row ``r`` attends positions ``< lengths[r]``.
    window : optional int — attend only the last ``window`` of them,
        and read the table as a RING: position ``p`` lives at entry
        ``(p // page_size) % pages_per_seq`` (a table as long as the
        context is the special case that never wraps). The kernel
        walks the ring's live entries, so a window layer costs its
        window, not its context.
    layer : int — which layer of a whole pool is read. It reaches the
        kernel as a scalar-prefetched operand, so a model's layers share
        one lowered kernel.

    Returns (b, kv_heads, group, head_dim). Forward-only (serving);
    no VJP is defined. On TPU this is a Mosaic kernel that walks each
    row's live pages, several a compute block, copying them itself from
    the scalar-prefetched block table (``_paged_kernel``): HBM traffic
    and loop trips are exactly the live pages of each sequence. How many
    pages make a block follows the page's bytes (``_pages_per_block``);
    nothing chooses it. Off-TPU, and for a pool Mosaic cannot slice by
    page (``_pages_can_be_copied``), the pure-lax gather twin runs —
    same contract, the tier-1 path.
    """
    b, kvh, g, hd = q.shape
    _check_layer(k_pages, layer)
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    # the twin: the production path off the TPU (not a python-interpreted
    # per-page DMA emulation; interpret=True still forces the interpreter
    # for kernel-logic tests), and on it for a pool Mosaic cannot slice
    if (interpret is None and not on_tpu(q)) or (
            not interpret and not _pages_can_be_copied(
                kvh, hd, k_pages.dtype.itemsize)):
        return _paged_decode_xla(q, k_pages, v_pages, block_tables, lengths,
                                 float(sm_scale), window, layer)
    k_pages, v_pages, layer = _whole_pools(k_pages, v_pages, layer)
    return _paged_decode(q, k_pages, v_pages, block_tables, lengths, layer,
                         float(sm_scale), bool(interpret),
                         None if window is None else int(window))


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "window"))
def _paged_decode(q, k_pages, v_pages, block_tables, lengths, layer,
                  sm_scale, interpret, window):
    """The jitted wrapper's NAME is what the device trace prints for the
    custom call (``_paged_decode.N``) and what the benchmark's kernel
    metrics look for: keep it."""
    b, kvh, g, hd = q.shape
    page_size = k_pages.shape[2]
    n_p = _pages_per_block(page_size, kvh, hd, k_pages.dtype.itemsize,
                           block_tables.shape[1])
    # q and o whole in VMEM, their heads flat: a free reshape of two
    # small arrays (the pools are the operands that are never reshaped)
    qo_spec = pl.BlockSpec((b, kvh * g, hd), lambda *_: (0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    buf = pltpu.VMEM((2, n_p, page_size, kvh, hd), k_pages.dtype)
    return pl.pallas_call(
        functools.partial(_paged_kernel, sm_scale=sm_scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[qo_spec, hbm_spec, hbm_spec], out_specs=qo_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(
            (b, kvh * g, hd), q.dtype, vma=_out_vma(q, k_pages, v_pages)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, layer, q.reshape(b, kvh * g, hd), k_pages,
      v_pages).reshape(q.shape)


# ---------------------------------------------------------------------------
# paged prefill attention (serving: one batched causal forward over the
# whole prompt bucket, with the reshape-scatter page write fused into
# the kernel as a DMA epilogue — prefill's XLA boundary the forensics
# worst-fusions report ranks worst is exactly this scatter round-trip)
# ---------------------------------------------------------------------------

def prefill_page_dest(block_tables, n_pb, page_size, lengths=None,
                      window=None):
    """(b, n_pb) pool page that each page of a prefill bucket is written
    to. Plainly, the table's first ``n_pb`` entries. With ``lengths``
    only the pages that hold a real position keep their place (the
    padded tail goes to the null page 0); with a ``window`` the table
    is a ring — page ``a`` at entry ``a % entries`` — and of the real
    pages only the last ``entries`` are kept, so that the padding can
    never wrap onto an entry the first decode steps still read."""
    if lengths is None and window is None:
        return block_tables[:, :n_pb]
    n_entries = block_tables.shape[1]
    a = jnp.arange(n_pb, dtype=jnp.int32)[None, :]
    if window is None:
        dest = block_tables[:, :n_pb]
    else:
        if lengths is None and n_pb > n_entries:
            raise ValueError(
                "a ring of %d entries cannot take a %d-page prompt "
                "without its length" % (n_entries, n_pb))
        dest = jnp.take_along_axis(
            block_tables, jnp.broadcast_to(a % n_entries, (
                block_tables.shape[0], n_pb)), axis=1)
    if lengths is not None:
        a_last = ((lengths - 1) // page_size)[:, None]
        keep = a <= a_last
        if window is not None:
            keep = jnp.logical_and(keep, a > a_last - n_entries)
        dest = jnp.where(keep, dest, 0)
    return dest


def causal_mask(s, window=None):
    """(s, s) bool: query i sees key j for ``0 <= i - j`` and, under a
    window, ``i - j < window``."""
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        mask = jnp.logical_and(mask, ~jnp.tril(jnp.ones((s, s), bool),
                                               -window))
    return mask


def _flash_prefill_xla(q, kg, vg, k_pages, v_pages, block_tables,
                       lengths=None, window=None, layer=None):
    """Pure-lax twin of :func:`flash_prefill_paged` — op-for-op the
    attention + page write of ``transformer._prefill_impl``'s paged
    branch (expand-KV einsum / sqrt(hd), tril mask, softmax, and the
    ``at[bt].set`` reshape-scatter — ``at[layer, bt]`` into a whole
    pool), so the CPU tier-1 prefill path and the dense==paged bitwise
    contract are this exact computation."""
    s, nh, hd = q.shape[1:]
    groups = nh // kg.shape[2]
    k = kg if groups == 1 else jnp.repeat(kg, groups, axis=2)
    v = vg if groups == 1 else jnp.repeat(vg, groups, axis=2)
    mask = causal_mask(s, window)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return (o,) + _scatter_pages(kg, vg, k_pages, v_pages, block_tables,
                                 lengths, window, layer)


def _scatter_pages(kg, vg, k_pages, v_pages, block_tables, lengths, window,
                   layer):
    """The prompt's K/V (b, s, kv_heads, hd) scattered to their pages
    (:func:`prefill_page_dest`), in place under jit: of one layer's
    pool, or of layer ``layer`` of a whole one."""
    b, s, kvh, hd = kg.shape
    ps = k_pages.shape[-3]
    n_pb = s // ps
    dest = prefill_page_dest(block_tables, n_pb, ps, lengths, window)
    at = dest if layer is None else (layer, dest)
    return (k_pages.at[at].set(
                kg.reshape(b, n_pb, ps, kvh, hd).astype(k_pages.dtype)),
            v_pages.at[at].set(
                vg.reshape(b, n_pb, ps, kvh, hd).astype(v_pages.dtype)))


def _prefill_kernel(bt_ref, layer_ref, *refs, sm_scale, block_q, block_k,
                    page_size, seq_len, n_heads, groups, write_pages,
                    by_length=False, window=None):
    """Grid (b, q_blocks, k_blocks): per (batch, q tile) the trailing k
    dimension accumulates an online softmax in VMEM scratch exactly
    like ``_fwd_kernel``, for every head in turn. The tiles keep the
    caller's ``(b, s, heads, hd)`` layout with the FULL trailing
    ``(heads, hd)`` dims — Mosaic refuses a per-head ``(1, block, 1,
    hd)`` block (second-minor dim 1 of ``heads``) — and K/V stay in the
    compact GQA layout: query head ``h`` reads K/V head ``h // groups``,
    never materialising the expanded (b, s, nh, hd) tensors the lax
    twin builds.

    With ``write_pages`` the page write rides the same pass: the first
    q-tile visit of each k block DMAs that block's freshly computed K/V
    straight from HBM into its rows' pages of layer ``layer_ref[0]`` of
    the WHOLE pool, which is aliased in->out and never sliced outside
    (``block_k`` is a multiple of ``page_size``, so each page is written
    exactly once per layer and the separate reshape-scatter program —
    and its HBM round-trip — disappears). ``by_length`` (a further
    scalar-prefetched array, the rows' real lengths) and ``window``
    choose which pages are written and where, as
    :func:`prefill_page_dest` says; a ``window`` also masks ``i - j >=
    window`` and skips the k blocks behind it."""
    if by_length:
        len_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref, *rest = refs
    if write_pages:
        (kg_ref, vg_ref, _kp_in, _vp_in, o_ref, kp_out, vp_out,
         m_scr, l_scr, acc_scr, ksem, vsem) = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b_i = pl.program_id(0)
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

    if write_pages:
        @pl.when(qi == 0)
        def _write_pages():
            layer = layer_ref[0]

            def copy_page(j, page):
                src = pl.ds(k_start + j * page_size, page_size)
                kcp = pltpu.make_async_copy(kg_ref.at[b_i, src],
                                            kp_out.at[layer, page], ksem)
                vcp = pltpu.make_async_copy(vg_ref.at[b_i, src],
                                            vp_out.at[layer, page], vsem)
                kcp.start()
                vcp.start()
                kcp.wait()
                vcp.wait()

            n_entries = bt_ref.shape[1]
            for j in range(block_k // page_size):
                a = ki * (block_k // page_size) + j
                entry = a if window is None else jax.lax.rem(a, n_entries)
                if not by_length:
                    copy_page(j, bt_ref[b_i, entry])
                    continue
                a_last = (len_ref[b_i] - 1) // page_size
                keep = a <= a_last
                if window is not None:
                    keep = jnp.logical_and(keep, a > a_last - n_entries)
                pl.when(keep)(functools.partial(
                    copy_page, j, bt_ref[b_i, entry]))

    def _body():
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        visible = jnp.logical_and(kpos < seq_len, kpos <= qpos)
        if window is not None:
            visible = jnp.logical_and(visible, qpos - kpos < window)
        for h in range(n_heads):
            q = q_ref[0, :, h, :].astype(jnp.float32) * sm_scale   # (bq, d)
            k = k_ref[0, :, h // groups, :].astype(jnp.float32)    # (bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)                # (bq, bk)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[h, :, :1] + jnp.sum(p, -1, keepdims=True)
            v = v_ref[0, :, h // groups, :].astype(jnp.float32)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)                # (bq, d)
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    # skip K blocks entirely above the causal diagonal (the page-write
    # epilogue above must NOT be skipped: padded-tail pages are still
    # written, exactly like the twin's scatter)
    in_reach = k_start <= q_start + block_q - 1
    if window is not None:
        # ... and those wholly behind every query's window (a row whose
        # own keys come later wipes what a masked block left: alpha = 0)
        in_reach = jnp.logical_and(
            in_reach, q_start - (k_start + block_k - 1) < window)

    @pl.when(in_reach)
    def _():
        _body()

    @pl.when(ki == nk - 1)
    def _fin():
        for h in range(n_heads):
            l = l_scr[h, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, h, :] = (acc_scr[h] / l_safe).astype(o_ref.dtype)


# VMEM the prefill tiles may claim (q and o tiles double-buffered by the
# pipeline + the f32 scratch): half of a v5e core's 16 MiB scoped limit,
# the rest is the K/V tiles and the compiler's own stack
_PREFILL_VMEM_BUDGET = 8 << 20


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "window"))
def _flash_prefill(q, kg, vg, k_pages, v_pages, block_tables, layer,
                   block_q, block_k, interpret, lengths, window):
    b, s, nh, hd = q.shape
    kvh = kg.shape[2]
    ps = k_pages.shape[2]
    # Mosaic can only slice an HBM ref whose minor dim fills whole
    # 128-lane tiles, so the DMA page write needs head_dim % 128 == 0;
    # narrower heads get the same attention kernel and the twin's
    # in-place XLA scatter for the pages
    write_pages = hd % _LANES == 0
    grid = (b, s // block_q, s // block_k)

    def q_map(b_i, qi, ki, *_prefetched):
        return (b_i, qi, 0, 0)

    def kv_map(b_i, qi, ki, *_prefetched):
        return (b_i, ki, 0, 0)

    q_spec = pl.BlockSpec((1, block_q, nh, hd), q_map)
    kv_spec = pl.BlockSpec((1, block_k, kvh, hd), kv_map)
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    scratch = [
        pltpu.VMEM((nh, block_q, _LANES), jnp.float32),
        pltpu.VMEM((nh, block_q, _LANES), jnp.float32),
        pltpu.VMEM((nh, block_q, hd), jnp.float32),
    ]
    kernel = functools.partial(
        _prefill_kernel, sm_scale=1.0 / math.sqrt(hd), block_q=block_q,
        block_k=block_k, page_size=ps, seq_len=s, n_heads=nh,
        groups=nh // kvh, write_pages=write_pages,
        by_length=lengths is not None, window=window)
    prefetched = (block_tables, layer) if lengths is None \
        else (block_tables, layer, lengths)
    n_pre = len(prefetched)
    vma = _out_vma(q, kg, vg, k_pages, v_pages)
    # the per-head store is strided over the heads dim, which Mosaic
    # cannot do on a packed (sub-32-bit) tile narrower than 128 lanes:
    # such outputs leave the kernel as f32 and are cast outside
    o_dtype = q.dtype if q.dtype.itemsize >= 4 or write_pages \
        else jnp.float32
    o_shape = jax.ShapeDtypeStruct((b, s, nh, hd), o_dtype, vma=vma)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if not write_pages:
        o = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_pre, grid=grid,
                in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
                scratch_shapes=scratch),
            out_shape=o_shape, compiler_params=params, interpret=interpret,
        )(*prefetched, q, kg, vg)
        return (o.astype(q.dtype),) + _scatter_pages(
            kg, vg, k_pages, v_pages, block_tables, lengths, window,
            layer[0])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, grid=grid,
            # kg/vg ride twice: blocked tiles for the attention, whole
            # HBM refs as the page-write source
            in_specs=[q_spec, kv_spec, kv_spec,
                      hbm_spec, hbm_spec, hbm_spec, hbm_spec],
            out_specs=[q_spec, hbm_spec, hbm_spec],
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA,
                                      pltpu.SemaphoreType.DMA]),
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype, vma=vma),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype, vma=vma),
        ],
        # the whole pools alias in->out: every other layer and the pages
        # no row writes keep their contents, and on TPU the pool is
        # updated in place (operand order counts the scalar-prefetch
        # args: bt=0, layer=1 ... k_pages=n_pre + 5)
        input_output_aliases={n_pre + 5: 1, n_pre + 6: 2},
        compiler_params=params,
        interpret=interpret,
    )(*prefetched, q, kg, vg, kg, vg, k_pages, v_pages)


def flash_prefill_paged(q, kg, vg, k_pages, v_pages, block_tables,
                        block_q=128, block_k=128, interpret=None,
                        lengths=None, window=None, layer=None):
    """Prefill-phase flash attention over a paged KV pool: one batched
    causal forward per layer whose epilogue writes the prompt's K/V
    pages, replacing ``(s, s)``-score XLA attention + a separate
    reshape-scatter program.

    Parameters
    ----------
    q : (b, s, n_heads, head_dim) — prompt queries (RoPE-rotated).
    kg, vg : (b, s, kv_heads, head_dim) — compact GQA K/V; the kernel
        never materialises the ``n_heads``-expanded copies.
    k_pages, v_pages : (layers, num_pages, page_size, kv_heads,
        head_dim) — the WHOLE pool of a kind of layer — with ``layer``;
        or one layer's 4-D pool, the same thing with one layer. Returned
        updated, in the shape given: the arrays alias in->out, so the
        caller takes them as its new pools. Never slice a pool by layer
        for this call and set the slice back: both are copies of the
        layer (``_paged_kernel`` says why).
    block_tables : (b, pages_per_row) int32 — destination page ids in
        position order (``pages_per_row = s // page_size``); rows of a
        warmup batch may all point at the reserved null page 0.
    lengths : optional (b,) int32 real prompt lengths — only pages that
        hold a real position are written (the padded tail is not).
    window : optional int — position ``i`` attends ``0 <= i - j <
        window`` only, and the table is a ring of ``block_tables.shape[1]``
        entries (page ``a`` at entry ``a % entries``): with ``lengths``
        just the last ``entries`` real pages are written. See
        :func:`prefill_page_dest`.
    layer : int — which layer of a whole pool is written (a scalar-
        prefetched operand of the kernel, as in the decode kernel).

    Returns ``(o, k_pages, v_pages)`` with ``o`` (b, s, n_heads,
    head_dim). Score scale is fixed at ``1/sqrt(head_dim)``. Causal
    only: position ``i`` attends ``<= i`` (ragged prompts rely on this
    plus the caller's final ``lengths-1`` logit gather, exactly like
    the XLA path). Forward-only (serving); no VJP. Off-TPU the
    pure-lax twin (the tier-1 path) runs; ``interpret=True`` forces
    the Pallas interpreter for parity tests."""
    b, s, nh, hd = q.shape
    _check_layer(k_pages, layer)
    ps = k_pages.shape[-3]
    if s % ps:
        raise ValueError("prefill bucket %d is not a multiple of "
                         "page_size %d" % (s, ps))
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if window is None:
        if s // ps > block_tables.shape[1]:
            raise ValueError("prefill bucket %d needs %d pages/row; "
                             "block table holds %d"
                             % (s, s // ps, block_tables.shape[1]))
        block_tables = block_tables[:, :s // ps]
    elif lengths is None and s // ps > block_tables.shape[1]:
        raise ValueError("a ring of %d entries cannot take a %d-page "
                         "prompt without its length"
                         % (block_tables.shape[1], s // ps))
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    if interpret is None:
        if not on_tpu(q):
            return _flash_prefill_xla(q, kg, vg, k_pages, v_pages,
                                      block_tables, lengths, window, layer)
        interpret = False
    # block_k must be a multiple of page_size (each page written by
    # exactly one k block) and divide s; block_q must divide s
    block_k = max(ps, (min(block_k, s) // ps) * ps)
    while s % block_k:
        block_k -= ps
    block_q = min(block_q, s)
    while s % block_q:
        block_q //= 2
    # every head of a q tile is resident at once: q and o tiles (double-
    # buffered) plus the f32 m/l/acc scratch, per q row
    row_bytes = nh * (2 * hd * (q.dtype.itemsize + 4)
                      + 4 * (2 * _LANES + hd))
    while block_q > 8 and block_q * row_bytes > _PREFILL_VMEM_BUDGET:
        block_q //= 2
    kp, vp, li = _whole_pools(k_pages, v_pages, layer)
    o, kp, vp = _flash_prefill(
        q, kg, vg, kp, vp, block_tables, li, int(block_q), int(block_k),
        bool(interpret), lengths, None if window is None else int(window))
    return (o, kp[0], vp[0]) if layer is None else (o, kp, vp)
