"""Mamba-1's selective scan (arXiv:2312.00752) as two Pallas TPU kernels:
a Mamba layer's recurrence over ONE token a row (decode) and over whole
prompts in chunks (prefill).

Per channel ``c`` (``d_inner`` of them) the state is ``d_state`` numbers,
zero at the sequence's start; for each token t with input ``x_t``, step
size ``delta_t > 0`` (a channel each) and the token's ``B_t``, ``C_t``
(``d_state`` each, shared by the channels):

    s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t
    y_t = s_t . C_t + D x_t

with ``A`` (d_state, channels) negative and ``D`` a skip a channel. A
token with ``delta_t = 0`` leaves the state as it is: that is how a
prompt's padding is told to the rule. The decay is a channel's AND a
state's, so no chunk of tokens collapses into one matrix product as in
Mamba-2 or a linear attention: both kernels walk the tokens in order on
the VPU, with the state laid out (d_state, channels) so that channels
fill the lanes.

``mamba_recurrent_step`` — a decode step. The states of all sequences
live in ONE pool ``(Mamba layers, rows, d_state, channels)`` float32 (row
0 the null row of dummy slots). The kernel takes the WHOLE pool, aliased
in -> out, with the layer's index and the batch rows' state rows as
scalar-prefetched operands: a grid step reads a row's state, updates it
and writes it back in place, and nothing else of the pool moves. The
step is bound by HBM: 2 x d_state x channels x 4 bytes a row.

``mamba_chunk_scan`` — prompts from their start, ``CHUNK`` tokens and
``_CHANNELS`` channels a grid step; the state is carried from chunk to
chunk in VMEM scratch and leaves the kernel once, after the last chunk. A
token's ``B`` and ``C`` columns are taken out of the chunk's transposed
block by a masked lane sum (no dynamic lane slice), eight tokens at a
time so that the read-outs are stored as whole sublane tiles.

Both are forward-only (serving); no VJP is defined. Everything is float32.
Off the TPU the ``jax.numpy`` twins run (the tier-1 path);
``interpret=True`` forces the Pallas interpreter for the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import on_tpu

__all__ = ["mamba_recurrent_step", "mamba_chunk_scan"]

PALLAS_KERNELS = {"mamba_recurrent_step": "_mamba_step_xla",
                  "mamba_chunk_scan": "_mamba_scan_xla"}

CHUNK = 128          # tokens a grid step of the prefill scan
_CHANNELS = 512      # channels a grid step of the prefill scan
_LANES = 128
_GROUP = 8           # tokens whose read-outs are stored together


def _step_rule(state, x, delta, bm, cm, a, d):
    """One token of the rule on the twin's shapes: state (..., d_state,
    channels), x and delta (..., channels), bm and cm (..., d_state), a
    (d_state, channels), d (channels,). Returns ``(state', y)``."""
    state = jnp.exp(delta[..., None, :] * a) * state \
        + (delta * x)[..., None, :] * bm[..., :, None]
    return state, jnp.sum(state * cm[..., :, None], -2) + d * x


def _f32(*ts):
    return tuple(t.astype(jnp.float32) for t in ts)


# ---------------------------------------------------------------------------
# one token a row, in place on the pool
# ---------------------------------------------------------------------------

def _mamba_step_xla(x, delta, bm, cm, a, d, pool, rows, layer):
    """``jax.numpy`` twin of :func:`mamba_recurrent_step` (the CPU tier-1
    path): the rows' states gathered, stepped, scattered back."""
    state, y = _step_rule(pool[layer, rows], *_f32(x, delta, bm, cm, a, d))
    return y, pool.at[layer, rows].set(state)


def _step_kernel(_layer_ref, _rows_ref, xd_ref, bc_ref, a_ref, d_ref, s_ref,
                 y_ref, s_out_ref):
    """Grid (rows,): one row's state (d_state, channels), read, stepped,
    written back. ``xd_ref`` (1, 2, channels): the token's x and delta;
    ``bc_ref`` (1, d_state, 2): its B and C as columns."""
    x, delta = xd_ref[0, 0:1, :], xd_ref[0, 1:2, :]          # (1, ch)
    bc = bc_ref[0]
    state = jnp.exp(delta * a_ref[...]) * s_ref[0, 0] + (delta * x) \
        * bc[:, 0:1]
    s_out_ref[0, 0] = state
    y_ref[0] = jnp.sum(state * bc[:, 1:2], 0, keepdims=True) \
        + d_ref[...] * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mamba_step(xd, bc, a, d, pool, rows, layer, interpret):
    """The jitted wrapper's NAME is what the device trace prints for the
    custom call (``_mamba_step.N``) and what the benchmark's kernel
    metrics look for: keep it."""
    b, _, ch = xd.shape
    ns = a.shape[0]

    def row_map(i, layer_ref, rows_ref):
        return (i, 0, 0)

    def whole(i, layer_ref, rows_ref):
        return (0, 0)

    def s_map(i, layer_ref, rows_ref):
        return (layer_ref[0], rows_ref[i], 0, 0)

    s_spec = pl.BlockSpec((1, 1, ns, ch), s_map)
    y, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, 2, ch), row_map),
                      pl.BlockSpec((1, ns, 2), row_map),
                      pl.BlockSpec((ns, ch), whole),
                      pl.BlockSpec((1, ch), whole), s_spec],
            out_specs=[pl.BlockSpec((1, 1, ch), row_map), s_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, ch), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool aliases in -> out (operands count the two prefetched)
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, rows, xd, bc, a, d, pool)
    return y[:, 0], pool


def mamba_recurrent_step(x, delta, bm, cm, a, d, pool, rows, layer,
                         interpret=None):
    """One token a row through the selective scan, the rows' states
    updated in place in their pool.

    Parameters
    ----------
    x, delta : (b, channels) — the token's input after the convolution,
        and its step size (float32; 0 leaves the state alone).
    bm, cm : (b, d_state) — the token's B and C.
    a : (d_state, channels) float32 — ``-exp(A_log)``, transposed.
    d : (channels,) — the skip.
    pool : (layers, rows, d_state, channels) float32 — the WHOLE state
        pool of the model's Mamba layers. Never slice it by layer for
        this call.
    rows : (b,) int32 — each batch row's state row (0: the null row).
    layer : int — which layer of the pool (a scalar-prefetched operand,
        so a model's layers share one lowered kernel).

    Returns ``(y (b, channels) float32, pool)``: ``s . C + D x`` of the
    updated states, and the pool with those states written (the array
    aliases in -> out). On the TPU a Mosaic kernel where the channels
    fill whole lane tiles; elsewhere the ``jax.numpy`` twin."""
    if not 0 <= layer < pool.shape[0]:
        raise ValueError("layer %r of a pool of %d layers"
                         % (layer, pool.shape[0]))
    rows = jnp.asarray(rows, jnp.int32)
    if interpret is None:
        if not on_tpu(x) or x.shape[-1] % _LANES:
            return _mamba_step_xla(x, delta, bm, cm, a, d, pool, rows,
                                   layer)
        interpret = False
    x, delta, bm, cm, a, d = _f32(x, delta, bm, cm, a, d)
    return _mamba_step(jnp.stack([x, delta], 1), jnp.stack([bm, cm], -1),
                       a, d[None], pool, rows,
                       jnp.asarray([layer], jnp.int32), bool(interpret))


# ---------------------------------------------------------------------------
# whole prompts, chunk by chunk
# ---------------------------------------------------------------------------

def _mamba_scan_xla(x, delta, bm, cm, a, d):
    """``jax.numpy`` twin of :func:`mamba_chunk_scan` (the CPU tier-1
    path): the rule token by token in a ``lax.scan``."""
    x, delta, bm, cm, a, d = _f32(x, delta, bm, cm, a, d)
    b, _s, ch = x.shape

    def step(state, xs):
        return _step_rule(state, *xs, a, d)

    lead = lambda t: jnp.moveaxis(t, 1, 0)
    state, y = jax.lax.scan(
        step, jnp.zeros((b, a.shape[0], ch), jnp.float32),
        tuple(lead(t) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1), state


def _scan_kernel(x_ref, dl_ref, bt_ref, ct_ref, a_ref, d_ref, y_ref,
                 s_out_ref, s_scr, *, chunk):
    """Grid (b, channel blocks, chunks); the chunks of a block are walked
    in order with the state in ``s_scr``. ``bt_ref``/``ct_ref`` (1,
    d_state, chunk): the chunk's B and C transposed, tokens on lanes."""
    c_i = pl.program_id(2)

    @pl.when(c_i == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    a = a_ref[...]                                        # (d_state, cb)
    dd = d_ref[...]                                       # (1, cb)
    bt, ct = bt_ref[0], ct_ref[0]                         # (d_state, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def group(gi, state):
        t0 = pl.multiple_of(gi * _GROUP, _GROUP)
        xs = x_ref[0, pl.ds(t0, _GROUP), :]              # (8, cb)
        ds = dl_ref[0, pl.ds(t0, _GROUP), :]
        ys = []
        for j in range(_GROUP):
            sel = lane == t0 + j
            b_t = jnp.sum(jnp.where(sel, bt, 0.0), 1, keepdims=True)
            c_t = jnp.sum(jnp.where(sel, ct, 0.0), 1, keepdims=True)
            x_t, d_t = xs[j:j + 1], ds[j:j + 1]
            state = jnp.exp(d_t * a) * state + (d_t * x_t) * b_t
            ys.append(jnp.sum(state * c_t, 0, keepdims=True) + dd * x_t)
        y_ref[0, pl.ds(t0, _GROUP), :] = jnp.concatenate(ys, 0)
        return state

    state = jax.lax.fori_loop(0, chunk // _GROUP, group, s_scr[...])
    s_scr[...] = state

    @pl.when(c_i == pl.num_programs(2) - 1)
    def _fin():
        s_out_ref[0] = state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _mamba_scan(x, delta, bt, ct, a, d, chunk, interpret):
    """The jitted wrapper's NAME is what the device trace prints for the
    custom call (``_mamba_scan.N``): keep it."""
    b, sp, ch = x.shape
    ns = a.shape[0]
    cb = min(_CHANNELS, ch)
    tok_spec = pl.BlockSpec((1, chunk, cb), lambda i, c, t: (i, t, c))
    bc_spec = pl.BlockSpec((1, ns, chunk), lambda i, c, t: (i, 0, t))
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=(b, ch // cb, sp // chunk),
        in_specs=[tok_spec, tok_spec, bc_spec, bc_spec,
                  pl.BlockSpec((ns, cb), lambda i, c, t: (0, c)),
                  pl.BlockSpec((1, cb), lambda i, c, t: (0, c))],
        out_specs=[tok_spec,
                   pl.BlockSpec((1, ns, cb), lambda i, c, t: (i, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((b, sp, ch), jnp.float32),
                   jax.ShapeDtypeStruct((b, ns, ch), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ns, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, delta, bt, ct, a, d)


def mamba_chunk_scan(x, delta, bm, cm, a, d, chunk=CHUNK, interpret=None):
    """Whole sequences, from a zero state, through the selective scan.

    Parameters
    ----------
    x, delta : (b, s, channels) — inputs after the convolution, step
        sizes (float32); a delta of 0 at a row's padding leaves the state
        as it was at the row's true length.
    bm, cm : (b, s, d_state) — every token's B and C.
    a : (d_state, channels) float32; d : (channels,).

    Returns ``(y (b, s, channels) float32, state (b, d_state, channels)
    float32)``: every position's read-out and the state after the last.
    ``s`` need not be a multiple of the chunk. On the TPU a Mosaic kernel
    where the channels fill whole lane tiles; elsewhere the
    ``jax.numpy`` twin."""
    if interpret is None:
        if not on_tpu(x) or x.shape[-1] % _LANES:
            return _mamba_scan_xla(x, delta, bm, cm, a, d)
        interpret = False
    x, delta, bm, cm, a, d = _f32(x, delta, bm, cm, a, d)
    s = x.shape[1]
    chunk = min(chunk, -(-s // _LANES) * _LANES)
    pad = -s % chunk
    if pad:        # tokens with delta 0 leave the state alone
        x, delta, bm, cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                            for t in (x, delta, bm, cm))
    y, state = _mamba_scan(x, delta, bm.transpose(0, 2, 1),
                           cm.transpose(0, 2, 1), a, d[None], int(chunk),
                           bool(interpret))
    return y[:, :s], state
