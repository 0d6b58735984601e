"""Gated DeltaNet (arXiv:2412.06464; the delta rule arXiv:2406.06484) as
two Pallas TPU kernels: the linear-attention layer's recurrence over ONE
token a row (decode) and over whole prompts in chunks (prefill).

Per value head, with ``S`` a (dk, dv) state (key x value), zero at the
sequence's start, for each token t with key ``k_t`` and query ``q_t``
(both L2-normalised, q times dk ** -0.5 by the caller), value ``v_t``,
log-decay ``g_t <= 0`` and write strength ``beta_t`` in [0, 1]:

    S <- S exp(g_t);  d = (v_t - S^T k_t) beta_t;  S <- S + k_t d^T
    o_t = S^T q_t

A token with ``g_t = 0`` and ``beta_t = 0`` leaves the state as it is:
that is how a prompt's padding is told to the rule.

``gdn_recurrent_step`` — a decode step. The states of all sequences live
in ONE pool ``(linear layers, rows, value heads, dk, dv)`` float32 (row 0
the null row of dummy slots). The kernel takes the WHOLE pool, aliased
in -> out, with the layer's index and the batch rows' state rows as
scalar-prefetched operands: a grid step reads a row's block of
``_HEAD_BLOCK`` heads, updates it and writes it back in place, and nothing
else of the pool moves (a ``pool[layer]`` in front of a custom call would
be a copy of the layer, ``flash_attention._paged_kernel`` says why). The
step is bound by HBM: 2 x dk x dv x 4 bytes a head a row against 7 dk dv
operations. Everything is float32 on the VPU — the products with the
state are broadcasts and sublane sums, exact — and the per-token vectors
ride in one lane-dense operand ``(b, 5, value heads, 128)``.

``gdn_chunk_prefill`` — a prompt from its start, 64 tokens a grid step in
the WY form (arXiv:2406.06484 sec. 3, arXiv:2412.06464 sec. 3.3): inside a
chunk, with ``G`` the running sum of g, ``A[i, j] = beta_i (k_i . k_j)
exp(G_i - G_j)`` for j < i, the tokens' corrected values are ``U = (I +
A)^-1 (V beta) - (I + A)^-1 (K beta exp(G)) S`` for the state ``S`` the
chunk began with, the outputs ``(Q exp(G)) S + tril(Q K^T exp(G_i - G_j))
U`` and the state after it ``S exp(G_last) + (K exp(G_last - G))^T U``.
``(I + A)^-1`` of the strictly lower triangular A is the product ``(I +
N)(I + N^2)(I + N^4) ...`` with N = -A (N^64 = 0): ten (64, 64, 64)
products on the MXU and no row-by-row substitution. The state is carried
from chunk to chunk in VMEM scratch and leaves the kernel once, after the
last chunk; float32 throughout (the products at the MXU's highest
precision).

Both are forward-only (serving); no VJP is defined. Off the TPU the
``jax.numpy`` twins run (the tier-1 path); ``interpret=True`` forces the
Pallas interpreter for the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import on_tpu

__all__ = ["gdn_recurrent_step", "gdn_chunk_prefill"]

PALLAS_KERNELS = {
    "gdn_recurrent_step": "_gdn_recurrent_xla",
    "gdn_chunk_prefill": "_gdn_chunk_xla",
}

CHUNK = 64
_LANES = 128
# value heads of one row a grid step of the recurrent kernel holds: eight
# (128, 128) float32 states are 512 KB in and as much out, 1.3 us of HBM
# time against a grid step's ~0.35 us of overhead
_HEAD_BLOCK = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def _per_value_head(t, value_heads, axis=-2):
    """q or k with its key heads on ``axis`` repeated so that value head j
    reads key head ``j // (value heads / key heads)``."""
    ratio = value_heads // t.shape[axis]
    return t if ratio == 1 else jnp.repeat(t, ratio, axis=axis)


# ---------------------------------------------------------------------------
# one token a row
# ---------------------------------------------------------------------------

def _gdn_recurrent_xla(q, k, v, g, beta, pool, rows, layer):
    """``jax.numpy`` twin of the recurrent kernel (the CPU tier-1 path):
    the rule as written, in float32, products with the state as
    broadcasts and sums. Shapes as :func:`gdn_recurrent_step`."""
    f32 = jnp.float32
    vh = v.shape[-2]
    q, k = (_per_value_head(t.astype(f32), vh) for t in (q, k))
    state = pool[layer, rows] * jnp.exp(g.astype(f32))[..., None, None]
    delta = (v.astype(f32) - jnp.sum(state * k[..., None], -2)) \
        * beta.astype(f32)[..., None]
    state = state + k[..., None] * delta[..., None, :]
    o = jnp.sum(state * q[..., None], -2)
    return o, pool.at[layer, rows].set(state)


def _recurrent_kernel(_layer_ref, _rows_ref, x_ref, s_ref, o_ref, s_out_ref,
                      *, heads, width):
    """Grid (rows, head blocks). ``x_ref`` (1, 5, heads, width): q, k, v,
    exp(g) and beta (the last two repeated over the lanes) of ``heads``
    value heads of one row; ``s_ref`` / ``s_out_ref`` (1, 1, heads, dk,
    dv): the same block of the pool, read and written."""
    i0 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    eye = i0 == i1

    def column(row):
        # (1, width) -> (width, 1) without a transpose: the diagonal of
        # the row repeated down the sublanes, summed over the lanes
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(
            row, (width, width)), 0.0), axis=1, keepdims=True)

    for h in range(heads):
        q, k, v, decay, beta = (x_ref[0, j, h:h + 1, :] for j in range(5))
        k_col = column(k)
        state = s_ref[0, 0, h] * decay
        delta = (v - jnp.sum(state * k_col, axis=0, keepdims=True)) * beta
        state = state + k_col * delta
        s_out_ref[0, 0, h] = state
        o_ref[0, h:h + 1, :] = jnp.sum(state * column(q), axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_recurrent(x, pool, rows, layer, interpret):
    b, _five, vh, width = x.shape
    heads = _HEAD_BLOCK if vh % _HEAD_BLOCK == 0 else vh

    def x_map(i, j, layer_ref, rows_ref):
        return (i, 0, j, 0)

    def o_map(i, j, layer_ref, rows_ref):
        return (i, j, 0)

    def s_map(i, j, layer_ref, rows_ref):
        return (layer_ref[0], rows_ref[i], j, 0, 0)

    s_spec = pl.BlockSpec((1, 1, heads, width, width), s_map)
    return pl.pallas_call(
        functools.partial(_recurrent_kernel, heads=heads, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, vh // heads),
            in_specs=[pl.BlockSpec((1, 5, heads, width), x_map), s_spec],
            out_specs=[pl.BlockSpec((1, heads, width), o_map), s_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, vh, width), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the whole pool aliases in -> out (operands count the scalar-
        # prefetched layer and rows: x is 2, the pool 3): every other
        # layer's and row's state stays where it is
        input_output_aliases={3: 1},
        # dummy slots share the null row: rows are walked in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer, rows, x, pool)


def gdn_recurrent_step(q, k, v, g, beta, pool, rows, layer, interpret=None):
    """One token a row through the gated delta rule, the rows' states
    updated in place in their pool.

    Parameters
    ----------
    q, k : (b, key heads, dk) — L2-normalised, q scaled; each key head
        serves ``value heads / key heads`` consecutive value heads.
    v : (b, value heads, dv).
    g, beta : (b, value heads) float32 — the decay's logarithm and the
        write strength.
    pool : (layers, rows, value heads, dk, dv) float32 — the WHOLE state
        pool of the model's linear layers. Never slice it by layer for
        this call.
    rows : (b,) int32 — each batch row's state row (0: the null row).
    layer : int — which layer of the pool (a scalar-prefetched operand,
        so a model's layers share one lowered kernel).

    Returns ``(o (b, value heads, dv) float32, pool)``: ``S^T q`` of the
    updated states, and the pool with those states written (the array
    aliases in -> out). On the TPU a Mosaic kernel where ``dk == dv`` is
    a multiple of 128 lanes; elsewhere the ``jax.numpy`` twin."""
    b, vh, dv = v.shape
    dk = q.shape[-1]
    if not 0 <= layer < pool.shape[0]:
        raise ValueError("layer %r of a pool of %d layers"
                         % (layer, pool.shape[0]))
    rows = jnp.asarray(rows, jnp.int32)
    if interpret is None:
        if not on_tpu(v) or dk != dv or dk % _LANES:
            return _gdn_recurrent_xla(q, k, v, g, beta, pool, rows, layer)
        interpret = False
    if dk != dv:
        raise ValueError("the kernel packs q, k and v in one operand: "
                         "dk=%d must equal dv=%d" % (dk, dv))
    f32 = jnp.float32
    lanes = lambda t: jnp.broadcast_to(t.astype(f32)[..., None], (b, vh, dv))
    x = jnp.stack([_per_value_head(q.astype(f32), vh),
                   _per_value_head(k.astype(f32), vh), v.astype(f32),
                   lanes(jnp.exp(g.astype(f32))), lanes(beta)], axis=1)
    o, pool = _gdn_recurrent(x, pool, rows, jnp.asarray([layer], jnp.int32),
                             bool(interpret))
    return o, pool


# ---------------------------------------------------------------------------
# a prompt in chunks
# ---------------------------------------------------------------------------

def _chunked(q, k, v, g, beta, chunk):
    """The kernel's and the twin's operands from the caller's: sequences
    padded to whole chunks with tokens that leave the state alone (g = 0,
    beta = 0), heads leading, g summed along each chunk. ``(q, k (b, key
    heads, s', dk), v (b, value heads, s', dv), G, beta (b, value heads,
    chunks, chunk))``."""
    f32 = jnp.float32
    s = q.shape[1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    b, sp, vh = g.shape

    def by_chunk(t):
        return t.astype(f32).transpose(0, 2, 1).reshape(b, vh, sp // chunk,
                                                        chunk)

    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), jnp.cumsum(by_chunk(g), -1),
            by_chunk(beta))


def _gdn_chunk_xla(q, k, v, g, beta, chunk=CHUNK):
    """``jax.numpy`` twin of the chunked kernel (the CPU tier-1 path): the
    same WY form, ``(I + A)^-1`` by a triangular solve, a ``lax.scan``
    over the chunks. Shapes as :func:`gdn_chunk_prefill`."""
    f32 = jnp.float32
    s, vh = q.shape[1], v.shape[2]
    qh, kh, vv, big_g, bt = _chunked(q, k, v, g, beta, chunk)
    b, _kh, sp, dk = qh.shape
    n_c = sp // chunk

    def chunks(t):                       # (b, vh, chunks, chunk, width)
        return _per_value_head(t.astype(f32), vh, axis=1).reshape(
            b, vh, n_c, chunk, -1)

    qc, kc, vc = chunks(qh), chunks(kh), chunks(vv)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta = kc * bt[..., None]

    def step(state, xs):
        q_i, k_i, u_i, w_i, qk_i, g_i = xs
        v_new = u_i - w_i @ state
        o = (q_i * jnp.exp(g_i)[..., None]) @ state + qk_i @ v_new
        last = g_i[..., -1:]
        state = state * jnp.exp(last)[..., None] + jnp.einsum(
            "...id,...iv->...dv", k_i * jnp.exp(last - g_i)[..., None],
            v_new)
        return state, o

    lead = lambda t: jnp.moveaxis(t, 2, 0)
    with jax.default_matmul_precision("highest"):
        a = jnp.where(
            jnp.tril(lower, -1),
            jnp.einsum("...id,...jd->...ij", k_beta, kc) * decay, 0.0)
        inv = jax.scipy.linalg.solve_triangular(
            jnp.eye(chunk, dtype=f32) + a,
            jnp.broadcast_to(jnp.eye(chunk, dtype=f32), a.shape),
            lower=True)
        u = inv @ (vc * bt[..., None])
        w = inv @ (k_beta * jnp.exp(big_g)[..., None])
        qk = jnp.einsum("...id,...jd->...ij", qc, kc) * decay
        state, o = jax.lax.scan(
            step, jnp.zeros((b, vh, dk, vc.shape[-1]), f32),
            tuple(lead(t) for t in (qc, kc, u, w, qk, big_g)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, vh, sp, -1)
    return o.transpose(0, 2, 1, 3)[:, :s].astype(v.dtype), state


def _chunk_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, s_out_ref, s_scr, *,
                  chunk):
    """Grid (b, value heads, chunks); the chunks of a head are walked in
    order with the state in ``s_scr``. ``gb_ref`` (1, 1, 1, 2, chunk):
    the chunk's running sum of g and its beta, as rows."""
    f32 = jnp.float32
    c_i = pl.program_id(2)

    @pl.when(c_i == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    def dot(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=f32)

    q = q_ref[0, 0].astype(f32)                           # (chunk, dk)
    k = k_ref[0, 0].astype(f32)
    v = v_ref[0, 0].astype(f32)                           # (chunk, dv)
    g_row = gb_ref[0, 0, 0, 0:1, :chunk]                  # (1, chunk)
    b_row = gb_ref[0, 0, 0, 1:2, :chunk]
    last_row = gb_ref[0, 0, 0, 2:3, :]                    # (1, lanes)
    i0 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = i0 == i1

    def column(row):
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(
            row, (chunk, chunk)), 0.0), axis=1, keepdims=True)

    g_col, b_col = column(g_row), column(b_row)
    lower = i0 >= i1
    # exp(G_i - G_j) for j <= i (an exponent above the diagonal could
    # overflow: it is never taken)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, g_col - g_row, 0.0)),
                      0.0)
    k_beta = k * b_col
    nt = ((1,), (1,))                                     # a b^T
    n = jnp.where(i0 > i1, -dot(k_beta, k, nt) * decay, 0.0)
    # (I - N)^-1 = (I + N)(I + N^2)(I + N^4) ... : N is nilpotent
    inv = jnp.where(eye, 1.0, 0.0) + n
    power = n
    for _ in range(max(chunk.bit_length() - 2, 0)):
        power = dot(power, power)
        inv = inv + dot(inv, power)
    state = s_scr[:]
    v_new = dot(inv, v * b_col) - dot(dot(inv, k_beta * jnp.exp(g_col)),
                                      state)
    o = dot(q * jnp.exp(g_col), state) + dot(dot(q, k, nt) * decay, v_new)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    state = state * jnp.exp(last_row[:, :state.shape[1]]) + dot(
        k * jnp.exp(column(last_row[:, :chunk]) - g_col), v_new,
        ((0,), (0,)))                                     # a^T b
    s_scr[:] = state

    @pl.when(c_i == pl.num_programs(2) - 1)
    def _fin():
        s_out_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_chunk(q, k, v, gb, chunk, interpret):
    b, kh, sp, dk = q.shape
    vh, dv = v.shape[1], v.shape[3]
    ratio = vh // kh

    def qk_map(i, h, c):
        return (i, h // ratio, c, 0)

    def v_map(i, h, c):
        return (i, h, c, 0)

    qk_spec = pl.BlockSpec((1, 1, chunk, dk), qk_map)
    v_spec = pl.BlockSpec((1, 1, chunk, dv), v_map)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk),
        grid=(b, vh, sp // chunk),
        in_specs=[qk_spec, qk_spec, v_spec,
                  pl.BlockSpec((1, 1, 1, 3, gb.shape[-1]),
                               lambda i, h, c: (i, h, c, 0, 0))],
        out_specs=[v_spec, pl.BlockSpec((1, 1, dk, dv),
                                        lambda i, h, c: (i, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, vh, sp, dv), v.dtype),
                   jax.ShapeDtypeStruct((b, vh, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, gb)


def gdn_chunk_prefill(q, k, v, g, beta, chunk=CHUNK, interpret=None):
    """Whole sequences, from a zero state, through the gated delta rule in
    chunks of ``chunk`` tokens.

    Parameters
    ----------
    q, k : (b, s, key heads, dk) — L2-normalised, q scaled.
    v : (b, s, value heads, dv).
    g, beta : (b, s, value heads) float32; 0 and 0 at a row's padding,
        which then leaves the state as it was at the row's true length.

    Returns ``(o (b, s, value heads, dv) in v's type, state (b, value
    heads, dk, dv) float32)``: every position's read-out and the state
    after the last. ``s`` need not be a multiple of the chunk. On the TPU
    a Mosaic kernel where dk and dv are multiples of 128 lanes; elsewhere
    the ``jax.numpy`` twin."""
    if interpret is None:
        if not on_tpu(v) or q.shape[-1] % _LANES or v.shape[-1] % _LANES:
            return _gdn_chunk_xla(q, k, v, g, beta, chunk)
        interpret = False
    s = q.shape[1]
    qh, kh, vv, big_g, bt = _chunked(q, k, v, g, beta, chunk)
    lanes = max(chunk, v.shape[-1])
    gb = jnp.stack([jnp.pad(t, ((0, 0),) * 3 + ((0, lanes - t.shape[-1]),))
                    for t in (big_g, bt, jnp.broadcast_to(
                        big_g[..., -1:], big_g.shape[:-1] + (lanes,)))],
                   axis=3)
    o, state = _gdn_chunk(qh, kh, vv, gb, int(chunk), bool(interpret))
    return o.transpose(0, 2, 1, 3)[:, :s], state
