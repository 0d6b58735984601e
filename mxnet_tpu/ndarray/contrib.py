"""nd.contrib namespace.

Reference: python/mxnet/ndarray/contrib.py (control flow foreach/
while_loop/cond) + generated _contrib_* op bindings (ROIAlign, box_nms,
MultiBoxPrior, CTCLoss, quantization, transformer helpers).
"""
from __future__ import annotations

from ..ops.control_flow import foreach, while_loop, cond  # noqa: F401
from .ndarray import invoke_op

__all__ = ["foreach", "while_loop", "cond", "ROIAlign", "box_iou",
           "bipartite_matching", "box_non_maximum_suppression",
           "box_nms", "MultiBoxPrior", "CTCLoss", "ctc_loss",
           "AdaptiveAvgPooling2D", "BilinearResize2D", "div_sqrt_dim",
           "arange_like", "dot_product_attention", "flash_attention", "quantize",
           "quantize_v2", "dequantize", "requantize",
           "quantized_fully_connected", "quantized_conv",
           "quantized_pooling", "quantized_flatten"]


def _wrap(op_name, public):
    from .ndarray import NDArray

    def fn(*args, **kwargs):
        arrays = []
        for i, a in enumerate(args):
            if isinstance(a, NDArray):
                arrays.append(a)
            elif a is not None:   # None = optional input slot (reference
                raise TypeError(  # convention, e.g. quantized FC bias)
                    "%s: positional argument %d is not an NDArray; pass "
                    "operator parameters by keyword" % (public, i))
        attrs = {k: v for k, v in kwargs.items()
                 if not isinstance(v, NDArray)}
        arrays += [v for v in kwargs.values() if isinstance(v, NDArray)]
        return invoke_op(op_name, arrays, attrs)
    fn.__name__ = public
    return fn


ROIAlign = _wrap("_contrib_ROIAlign", "ROIAlign")
box_iou = _wrap("_contrib_box_iou", "box_iou")
box_nms = _wrap("_contrib_box_nms", "box_nms")
MultiBoxPrior = _wrap("_contrib_MultiBoxPrior", "MultiBoxPrior")
CTCLoss = _wrap("CTCLoss", "CTCLoss")
ctc_loss = CTCLoss
AdaptiveAvgPooling2D = _wrap("_contrib_AdaptiveAvgPooling2D",
                             "AdaptiveAvgPooling2D")
BilinearResize2D = _wrap("_contrib_BilinearResize2D", "BilinearResize2D")
div_sqrt_dim = _wrap("_contrib_div_sqrt_dim", "div_sqrt_dim")
arange_like = _wrap("_contrib_arange_like", "arange_like")
bipartite_matching = _wrap("_contrib_bipartite_matching",
                           "bipartite_matching")
box_non_maximum_suppression = _wrap("_contrib_box_nms",
                                    "box_non_maximum_suppression")
dot_product_attention = _wrap("_contrib_dot_product_attention",
                              "dot_product_attention")
def flash_attention(q, k, v, **kwargs):
    """Pallas flash attention (ops/pallas/flash_attention.py). The
    interpret flag is resolved here from the data's actual device —
    inside the op jit only tracers are visible."""
    if "interpret" not in kwargs:
        from ..ops.pallas.flash_attention import on_tpu
        kwargs["interpret"] = not on_tpu(q._data)
    return invoke_op("_contrib_flash_attention", [q, k, v], kwargs)
quantize = _wrap("_contrib_quantize", "quantize")
quantize_v2 = _wrap("_contrib_quantize_v2", "quantize_v2")
dequantize = _wrap("_contrib_dequantize", "dequantize")
requantize = _wrap("_contrib_requantize", "requantize")
quantized_fully_connected = _wrap("_contrib_quantized_fully_connected",
                                  "quantized_fully_connected")
quantized_conv = _wrap("_contrib_quantized_conv", "quantized_conv")
quantized_pooling = _wrap("_contrib_quantized_pooling", "quantized_pooling")
quantized_flatten = _wrap("_contrib_quantized_flatten", "quantized_flatten")


def _populate_generated():
    """Expose every registered ``_contrib_*`` op under its public name,
    mirroring the reference's generated contrib bindings
    (python/mxnet/ndarray/register.py)."""
    from ..ops import registry as _reg
    g = globals()
    for op_name in _reg.list_ops():
        if not op_name.startswith("_contrib_"):
            continue
        public = op_name[len("_contrib_"):]
        if public not in g:
            g[public] = _wrap(op_name, public)
            __all__.append(public)


_populate_generated()


def __getattr__(name):  # PEP 562: resolve late-registered contrib ops
    from ..ops import registry as _reg
    op_name = "_contrib_" + name
    if op_name in _reg.list_ops():
        fn = _wrap(op_name, name)
        globals()[name] = fn
        return fn
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))
