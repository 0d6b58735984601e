"""Operations of one ResNet (v2, as ``models/resnet.py`` and the
reference symbol build it) training step, computed from the parameter
shapes the module bound and the image size — nothing is hard-coded.

Counted: the multiply-accumulates of every convolution and of the fully
connected layer, forward. A training step needs the forward pass, the
gradient with respect to the activations and the gradient with respect to
the weights, each the same multiply-accumulates: 3 x forward, 2 FLOP a
multiply-accumulate, nothing recomputed. Batch-norm, ReLU, pooling and the
optimizer are elementwise and left out, as is usual for a model FLOP
utilization. ResNet-50 at 224x224: about 4.1 GMAC forward, 24.6 GFLOP a
row forward and backward.
"""
import re


def forward_macs_per_row(param_shapes, image_shape):
    """``param_shapes``: name -> shape, by the symbol's names;
    ``image_shape``: (channels, height, width)."""
    _c, h, w = image_shape
    assert h == w, "square images only"
    macs = 0

    def conv(name, size_out):
        cout, cin, kh, kw = param_shapes[name + "_weight"]
        return size_out * size_out * cout * cin * kh * kw

    if "bn0_gamma" in param_shapes:         # 7x7 stride 2, then 3x3 pool /2
        size = (h + 2 * 3 - 7) // 2 + 1
        macs += conv("conv0", size)
        size = (size + 2 * 1 - 3) // 2 + 1
    else:
        size = h
        macs += conv("conv0", size)
    units = sorted(set(
        (int(m.group(1)), int(m.group(2))) for m in
        (re.match(r"stage(\d+)_unit(\d+)_conv1_weight$", n)
         for n in param_shapes) if m))
    for stage, unit in units:
        name = "stage%d_unit%d" % (stage, unit)
        stride = 2 if unit == 1 and stage > 1 else 1
        out = (size - 1) // stride + 1      # 3x3 pad 1 / 1x1 pad 0
        if name + "_conv3_weight" in param_shapes:
            macs += conv(name + "_conv1", size)     # 1x1 before the stride
            macs += conv(name + "_conv2", out)      # 3x3 carries the stride
            macs += conv(name + "_conv3", out)
        else:
            macs += conv(name + "_conv1", out)
            macs += conv(name + "_conv2", out)
        if name + "_sc_weight" in param_shapes:
            macs += conv(name + "_sc", out)
        size = out
    classes, features = param_shapes["fc1_weight"]
    macs += classes * features
    return macs


def train_flops_per_row(param_shapes, image_shape):
    return 3 * 2 * forward_macs_per_row(param_shapes, image_shape)


def parameter_count(param_shapes):
    n = 0
    for shape in param_shapes.values():
        k = 1
        for d in shape:
            k *= d
        n += k
    return n
