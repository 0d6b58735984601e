"""INT8 quantization operators.

Reference: src/operator/quantization/ (quantize.cc, dequantize.cc,
requantize.cc, quantized_conv.cc, quantized_fully_connected.cc,
quantized_pooling.cc). TPU-native: int8 arithmetic feeds the MXU via
XLA's integer dot/conv; min/max calibration ranges ride along as extra
outputs exactly like the reference's (out, min, max) triples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register, get_op

_INT8_MIN, _INT8_MAX = -127.0, 127.0


def _safe_div(num, denom):
    """``num / denom`` with a zero denominator mapping to 1.0 — the
    denominator is substituted BEFORE the division, so the other branch
    never computes inf/NaN (a plain ``where(d > 0, num / d, 1.0)``
    still evaluates ``num / 0`` and, multiplied downstream, turns a
    zero-range tensor into NaN output; see the round-trip tests)."""
    denom = jnp.asarray(denom, jnp.float32)
    safe = jnp.where(denom > 0, denom, 1.0)
    return jnp.where(denom > 0, jnp.asarray(num, jnp.float32) / safe, 1.0)


def _range_scale(min_r, max_r):
    """127 / amax for a (min, max) range; 1.0 for a zero/degenerate
    range (a constant-zero tensor quantizes to zeros and dequantizes
    back to zeros, never NaN)."""
    amax = jnp.maximum(jnp.abs(min_r), jnp.abs(max_r))
    return _safe_div(_INT8_MAX, amax)


@register("_contrib_quantize", num_outputs=3, differentiable=False,
          attr_defaults={"out_type": "int8"})
def _quantize(data, min_range, max_range, out_type="int8", **_ig):
    """fp32 -> int8 with explicit range (reference: quantize.cc).
    Returns (q, min, max)."""
    scale = _range_scale(min_range, max_range)
    q = jnp.clip(jnp.round(data * scale), _INT8_MIN, _INT8_MAX) \
        .astype(jnp.int8)
    return q, min_range.reshape(()), max_range.reshape(())


@register("_contrib_quantize_v2", num_outputs=3, differentiable=False,
          attr_defaults={"out_type": "int8", "min_calib_range": None,
                         "max_calib_range": None})
def _quantize_v2(data, out_type="int8", min_calib_range=None,
                 max_calib_range=None, **_ig):
    """fp32 -> int8, range from calibration or the data itself
    (reference: quantize_v2.cc)."""
    if min_calib_range is not None and max_calib_range is not None:
        mn = jnp.asarray(min_calib_range, dtype=jnp.float32)
        mx = jnp.asarray(max_calib_range, dtype=jnp.float32)
    else:
        mn = jnp.min(data)
        mx = jnp.max(data)
    scale = _range_scale(mn, mx)
    q = jnp.clip(jnp.round(data * scale), _INT8_MIN, _INT8_MAX) \
        .astype(jnp.int8)
    return q, mn.reshape(()), mx.reshape(())


@register("_contrib_dequantize", attr_defaults={"out_type": "float32"})
def _dequantize(data, min_range, max_range, out_type="float32", **_ig):
    """int8 -> fp32 (reference: dequantize.cc)."""
    scale = _range_scale(min_range, max_range)
    return data.astype(jnp.float32) / scale


@register("_contrib_requantize", num_outputs=3, differentiable=False,
          attr_defaults={"min_calib_range": None, "max_calib_range": None})
def _requantize(data, min_range, max_range, min_calib_range=None,
                max_calib_range=None, **_ig):
    """int32 accumulators -> int8 (reference: requantize.cc)."""
    real = data.astype(jnp.float32) * (
        jnp.maximum(jnp.abs(min_range), jnp.abs(max_range))
        / (2.0 ** 31 - 1))
    if min_calib_range is not None:
        mn = jnp.asarray(min_calib_range, jnp.float32)
        mx = jnp.asarray(max_calib_range, jnp.float32)
    else:
        mn = jnp.min(real)
        mx = jnp.max(real)
    scale = _range_scale(mn, mx)
    q = jnp.clip(jnp.round(real * scale), _INT8_MIN, _INT8_MAX) \
        .astype(jnp.int8)
    return q, mn.reshape(()), mx.reshape(())


def _q_range_out(x_int32, min_a, max_a, min_b, max_b):
    """Range of an int32 accumulation of int8*int8 products."""
    scale_a = _range_scale(min_a, max_a)
    scale_b = _range_scale(min_b, max_b)
    real = x_int32.astype(jnp.float32) / (scale_a * scale_b)
    return real


@register("_contrib_quantized_fully_connected", num_outputs=3, differentiable=False,
          attr_defaults={"num_hidden": 0, "no_bias": False, "flatten": True})
def _quantized_fc(*arrays, num_hidden=0, no_bias=False, flatten=True,
                  **_ig):
    """INT8 FC with int32 accumulation on the MXU
    (reference: quantized_fully_connected.cc). Returns fp32-equivalent
    int32 outputs + ranges; chain with requantize.

    Inputs (reference order): data, weight[, bias], min_data, max_data,
    min_weight, max_weight[, min_bias, max_bias]."""
    if no_bias or len(arrays) == 6:
        data, weight, min_data, max_data, min_weight, max_weight = arrays
        bias = min_bias = max_bias = None
        no_bias = True
    else:
        (data, weight, bias, min_data, max_data, min_weight, max_weight,
         min_bias, max_bias) = arrays
    x = data.astype(jnp.int32)
    if flatten and x.ndim > 2:
        x = x.reshape((x.shape[0], -1))
    out = lax.dot_general(
        x, weight.astype(jnp.int32),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    real = _q_range_out(out, min_data, max_data, min_weight, max_weight)
    if not no_bias and bias is not None:
        scale_b = _range_scale(min_bias, max_bias)
        real = real + bias.astype(jnp.float32) / scale_b
    mn = jnp.min(real)
    mx = jnp.max(real)
    scale = _safe_div(2.0 ** 31 - 1, jnp.maximum(jnp.abs(mn), jnp.abs(mx)))
    q32 = jnp.round(real * scale).astype(jnp.int32)
    return q32, mn.reshape(()), mx.reshape(())


@register("_contrib_quantized_conv", num_outputs=3, differentiable=False,
          attr_defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                         "num_filter": 0, "num_group": 1, "no_bias": True,
                         "layout": None})
def _quantized_conv(data, weight, min_data, max_data, min_weight,
                    max_weight, kernel=(), stride=(), dilate=(), pad=(),
                    num_filter=0, num_group=1, no_bias=True, layout=None,
                    **_ig):
    """INT8 convolution (reference: quantized_conv.cc)."""
    nd = len(kernel)
    stride = tuple(stride) or (1,) * nd
    dilate = tuple(dilate) or (1,) * nd
    pad = tuple(pad) or (0,) * nd
    dims = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dims)
    out = lax.conv_general_dilated(
        data.astype(jnp.int32), weight.astype(jnp.int32),
        window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.int32)
    real = _q_range_out(out, min_data, max_data, min_weight, max_weight)
    mn = jnp.min(real)
    mx = jnp.max(real)
    scale = _safe_div(2.0 ** 31 - 1, jnp.maximum(jnp.abs(mn), jnp.abs(mx)))
    q32 = jnp.round(real * scale).astype(jnp.int32)
    return q32, mn.reshape(()), mx.reshape(())


@register("_contrib_quantized_pooling", num_outputs=3,
          differentiable=False,
          attr_defaults={"kernel": (), "pool_type": "max",
                         "global_pool": False, "stride": (), "pad": (),
                         "pooling_convention": "valid"})
def _quantized_pooling(data, min_data, max_data, kernel=(), pool_type="max",
                       global_pool=False, stride=(), pad=(),
                       pooling_convention="valid", **_ig):
    """INT8 pooling (reference: quantized_pooling.cc): pool in int8,
    ranges pass through."""
    pool = get_op("Pooling")
    out = pool.fn(data.astype(jnp.float32), kernel=kernel,
                  pool_type=pool_type, global_pool=global_pool,
                  stride=stride, pad=pad,
                  pooling_convention=pooling_convention)
    return out.astype(data.dtype), min_data.reshape(()), \
        max_data.reshape(())


@register("_contrib_quantized_flatten", num_outputs=3, differentiable=False)
def _quantized_flatten(data, min_data, max_data):
    return data.reshape((data.shape[0], -1)), min_data.reshape(()), \
        max_data.reshape(())


# ---------------------------------------------------------------------------
# per-channel serving ops (mxnet_tpu/quantize/ PTQ artifacts)
#
# Unlike the (out, min, max)-triple reference ops above — which chain
# quantize_v2 -> quantized_op -> requantize -> dequantize as separate
# graph nodes with per-TENSOR dynamic ranges — these are the
# first-class quantized-serving kernels: per-CHANNEL int8 weights with
# fp32 scales live as graph parameters, the activation scale is a
# static attr baked from calibration, and the whole
# quantize -> int8 dot -> rescale -> bias runs as ONE op whose rescale
# is a dot epilogue (Pallas kernel on TPU, fused by XLA off it), never
# a separate dequantize node.
# ---------------------------------------------------------------------------

def _quantize_act(data, act_scale):
    """fp32 activations -> int8 with a static calibrated scale."""
    return jnp.clip(jnp.round(data.astype(jnp.float32)
                              * jnp.float32(act_scale)),
                    _INT8_MIN, _INT8_MAX).astype(jnp.int8)


@register("_contrib_quantized_fc_int8", differentiable=False,
          attr_defaults={"num_hidden": 0, "no_bias": False, "flatten": True,
                         "act_scale": 1.0})
def _quantized_fc_int8(data, weight, scale, bias=None, num_hidden=0,
                       no_bias=False, flatten=True, act_scale=1.0, **_ig):
    """Per-channel INT8 fully connected for quantized serving.

    Inputs: ``data`` fp32, ``weight`` int8 ``(num_hidden, k)`` quantized
    per output channel, ``scale`` fp32 ``(num_hidden,)`` = per-channel
    weight scales (``w ~= weight * scale[:, None]``), optional ``bias``
    fp32. ``act_scale`` (static, from calibration) maps activations to
    int8: ``q = round(data * act_scale)``. Output is fp32:
    ``(q . weight^T) * (scale / act_scale) + bias`` with the rescale
    fused into the int8 matmul epilogue (ops/pallas/int8_matmul.py)."""
    from .pallas.int8_matmul import int8_matmul
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape((x.shape[0], -1))
    lead = x.shape[:-1]
    q = _quantize_act(x.reshape((-1, x.shape[-1])), act_scale)
    out_scale = scale.astype(jnp.float32) / jnp.float32(act_scale)
    out = int8_matmul(q, weight.astype(jnp.int8), out_scale)
    if bias is not None and not no_bias:
        out = out + bias.astype(jnp.float32)
    return out.reshape(lead + (out.shape[-1],))


@register("_contrib_quantized_conv_int8", differentiable=False,
          attr_defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                         "num_filter": 0, "num_group": 1, "no_bias": False,
                         "layout": None, "act_scale": 1.0})
def _quantized_conv_int8(data, weight, scale, bias=None, kernel=(),
                         stride=(), dilate=(), pad=(), num_filter=0,
                         num_group=1, no_bias=False, layout=None,
                         act_scale=1.0, **_ig):
    """Per-channel INT8 convolution for quantized serving: int8
    operands, int32 accumulation, per-output-channel rescale fused into
    the conv's epilogue by XLA (NCHW-family layouts; channel = filter
    axis 0). Same scale contract as ``_contrib_quantized_fc_int8``."""
    nd = len(kernel)
    stride = tuple(stride) or (1,) * nd
    dilate = tuple(dilate) or (1,) * nd
    pad = tuple(pad) or (0,) * nd
    dims = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    q = _quantize_act(data, act_scale)
    if nd == 2:
        from .. import config as _config
        from .pallas.flash_attention import on_tpu
        if on_tpu(data) or _config.get("MXNET_INT8_CONV_IM2COL"):
            # im2col route: lower the 2-D conv onto the int8 MXU matmul
            # kernel with the per-channel rescale fused in its epilogue
            # (the PR 11 escape hatch). int32 accumulation is exact, so
            # this is BITWISE the lax conv route below.
            from .pallas.int8_matmul import int8_conv_im2col
            out_scale = (scale.astype(jnp.float32)
                         / jnp.float32(act_scale))
            out = int8_conv_im2col(q, weight.astype(jnp.int8),
                                   out_scale, stride, dilate, pad,
                                   num_group)
            if bias is not None and not no_bias:
                out = out + bias.astype(jnp.float32).reshape(1, -1, 1, 1)
            return out
    dn = lax.conv_dimension_numbers(q.shape, weight.shape, dims)
    acc = lax.conv_general_dilated(
        q.astype(jnp.int32), weight.astype(jnp.int8).astype(jnp.int32),
        window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.int32)
    chan = (1, -1) + (1,) * nd
    out = acc.astype(jnp.float32) * (
        scale.astype(jnp.float32) / jnp.float32(act_scale)).reshape(chan)
    if bias is not None and not no_bias:
        out = out + bias.astype(jnp.float32).reshape(chan)
    return out
