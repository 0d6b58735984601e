"""INT8 matmul with a fused per-channel rescale epilogue, as a Pallas
TPU kernel.

The serving-side hot op of the quantized inference path
(mxnet_tpu/quantize/): ``out[m, n] = (x_q[m, :] . w_q[n, :]) *
scale[n]`` where ``x_q``/``w_q`` are int8, the dot accumulates in int32
on the MXU, and the per-output-channel fp32 rescale happens INSIDE the
kernel epilogue — the int32 accumulator never round-trips through HBM
and no separate dequantize op exists for XLA to schedule apart from the
dot (the "Operator Fusion in XLA" framing: the rescale is an epilogue,
not a graph node).

Grid (m_blocks, n_blocks, k_blocks); the trailing k dimension iterates
sequentially per (m, n) tile, accumulating into an int32 VMEM scratch
exactly like flash attention's online-softmax accumulator; the last k
step multiplies by the (1, block_n) scale tile and writes fp32.

Off-TPU the pure-lax twin (``dot_general`` with
``preferred_element_type=int32`` + broadcast rescale) is the production
path — the tier-1 reference the kernel is parity-tested against in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _out_vma, _pad_to, on_tpu

__all__ = ["int8_matmul", "int8_conv_im2col"]

# kernel-contract registry: exported kernel -> module-level pure-lax
# twin (see tools/check_pallas_contracts.py)
PALLAS_KERNELS = {
    "int8_matmul": "_int8_matmul_xla",
    "int8_conv_im2col": "_int8_conv_xla",
}


def _int8_matmul_xla(x, w, scale):
    """Pure-lax twin of the kernel (same contract): int8 operands, int32
    MXU accumulation, per-channel fp32 rescale. XLA fuses the rescale
    into the dot's epilogue on TPU; on CPU this is the tier-1 path."""
    acc = lax.dot_general(
        x.astype(jnp.int8), w.astype(jnp.int8),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                    # (m, n)
    return acc.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # int8 x int8 -> int32 on the MXU; accumulate across k blocks
    acc_scr[:] += lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                    # (bm, bn)

    @pl.when(ki == nk - 1)
    def _fin():
        # fused epilogue: per-output-channel rescale, int32 -> fp32
        o_ref[:] = acc_scr[:].astype(jnp.float32) * s_ref[:]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def _int8_matmul_pallas(x, w, scale, block_m, block_n, block_k, interpret):
    m, k = x.shape
    n = w.shape[0]
    xf = _pad_to(_pad_to(x, block_m, 0), block_k, 1)
    wf = _pad_to(_pad_to(w, block_n, 0), block_k, 1)
    sf = _pad_to(scale.astype(jnp.float32).reshape(1, n), block_n, 1)
    grid = (xf.shape[0] // block_m, wf.shape[0] // block_n,
            xf.shape[1] // block_k)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_n, block_k), lambda mi, ni, ki: (ni, ki)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct(
            (xf.shape[0], wf.shape[0]), jnp.float32,
            vma=_out_vma(x, w, scale)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xf, wf, sf)
    return out[:m, :n]


def int8_matmul(x, w, scale, block_m=128, block_n=128, block_k=128,
                interpret=None):
    """``(x . w^T) * scale[None, :]`` with int8 operands and int32 MXU
    accumulation.

    Parameters
    ----------
    x : (m, k) int8 — quantized activations.
    w : (n, k) int8 — per-channel-quantized weights (channel = axis 0).
    scale : (n,) float32 — fused epilogue factor per output channel
        (``w_scale[n] / act_scale`` for a quantized dense layer).
    block_m, block_n, block_k : VMEM tile sizes (multiples of the int8
        tile (32, 128) on TPU; inputs are zero-padded to block
        multiples, and zero int8 products contribute nothing).
    interpret : force pallas interpreter mode. Default: the compiled
        Mosaic kernel on TPU, the pure-lax twin elsewhere (int32
        accumulation is exact, so twin and kernel agree BITWISE —
        asserted by tests/test_quantize.py in interpret mode).
    """
    x = x.astype(jnp.int8)
    w = w.astype(jnp.int8)
    if interpret is None:
        if not on_tpu(x):
            return _int8_matmul_xla(x, w, scale)
        interpret = False
    m, k = x.shape

    def _ceil(v, mult):
        return -(-v // mult) * mult

    # tile-legal block shrink for small operands: block_m is an int8
    # SUBLANE dim (x block) -> multiple of 32; block_n is w's sublane
    # AND the fp32 out/scale LANE dim -> multiple of 128; block_k is
    # the int8 lane dim -> multiple of 128. (Inputs are zero-padded to
    # block multiples, so rounding UP never changes results.)
    block_m = min(block_m, _ceil(m, 32))
    block_n = min(block_n, _ceil(w.shape[0], 128))
    block_k = min(block_k, _ceil(k, 128))
    return _int8_matmul_pallas(x, w, scale, int(block_m), int(block_n),
                               int(block_k), bool(interpret))


# ---------------------------------------------------------------------------
# int8 conv via im2col — the PR 11 escape hatch: when XLA's epilogue
# fusion of conv + dequant falls short, lower the conv onto the SAME
# int8 MXU matmul kernel above (rescale stays fused in the epilogue)
# ---------------------------------------------------------------------------

def _int8_conv_xla(q, wq, scale, stride, dilate, pad, num_group):
    """Pure-lax twin of :func:`int8_conv_im2col`: the direct
    ``conv_general_dilated`` int32 route `_contrib_quantized_conv_int8`
    has always used (int32 accumulation is exact, so twin and im2col
    agree BITWISE)."""
    dn = lax.conv_dimension_numbers(q.shape, wq.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    acc = lax.conv_general_dilated(
        q.astype(jnp.int32), wq.astype(jnp.int8).astype(jnp.int32),
        window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * scale.astype(
        jnp.float32).reshape(1, -1, 1, 1)


def _im2col(q, kh, kw, stride, dilate, pad):
    """Unfold NCHW int8 activations into patch rows: strided slices
    (one per kernel tap — cheap layout ops XLA folds into the copy)
    stacked so the contraction axis orders (cin, kh, kw), matching
    ``wq.reshape(cout, -1)``."""
    b, cin, h, w = q.shape
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    oh = (h + 2 * ph - ((kh - 1) * dh + 1)) // sh + 1
    ow = (w + 2 * pw - ((kw - 1) * dw + 1)) // sw + 1
    xp = jnp.pad(q, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    cols = []
    for ki in range(kh):
        for kj in range(kw):
            cols.append(lax.slice(
                xp, (0, 0, ki * dh, kj * dw),
                (b, cin, ki * dh + (oh - 1) * sh + 1,
                 kj * dw + (ow - 1) * sw + 1),
                (1, 1, sh, sw)))                     # (b, cin, oh, ow)
    # (kh*kw, b, cin, oh, ow) -> (b, oh, ow, cin, kh*kw)
    patches = jnp.stack(cols).transpose(1, 3, 4, 2, 0)
    return patches.reshape(b * oh * ow, cin * kh * kw), oh, ow


def int8_conv_im2col(q, wq, scale, stride, dilate, pad, num_group=1,
                     interpret=None):
    """2-D int8 convolution lowered onto the int8 MXU matmul.

    Parameters
    ----------
    q : (b, cin, h, w) int8 — quantized NCHW activations.
    wq : (cout, cin // num_group, kh, kw) int8 — OIHW weights.
    scale : (cout,) float32 — fused per-channel epilogue factor
        (``w_scale / act_scale`` for the quantized conv op).
    stride, dilate, pad : 2-tuples (symmetric padding).
    interpret : forwarded to :func:`int8_matmul`; ``None`` keeps the
        kernel dispatch contract (Mosaic on TPU, the matmul's lax twin
        off-TPU — int32 accumulation makes every route bitwise equal
        to :func:`_int8_conv_xla`).

    Returns (b, cout, oh, ow) float32.
    """
    cout, _, kh, kw = wq.shape
    cout_g = cout // num_group
    cin_g = wq.shape[1]
    outs = []
    for gi in range(num_group):
        qg = q[:, gi * cin_g:(gi + 1) * cin_g]
        wg = wq[gi * cout_g:(gi + 1) * cout_g]
        sg = scale[gi * cout_g:(gi + 1) * cout_g]
        patches, oh, ow = _im2col(qg, kh, kw, stride, dilate, pad)
        outs.append(int8_matmul(patches, wg.reshape(cout_g, -1), sg,
                                interpret=interpret))
    out = jnp.concatenate(outs, axis=-1) if num_group > 1 else outs[0]
    b = q.shape[0]
    return out.reshape(b, oh, ow, cout).transpose(0, 3, 1, 2)
