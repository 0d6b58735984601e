"""The functions that compute operations and bytes from shapes, each
against a hand count. CPU only; no jax import."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402

CELL = harness.Cell(ROOT, "resnet50.fit_1chip")


def resnet50_shapes():
    """ResNet-50's convolution and FC shapes by the symbol's names, written
    out from the architecture (stages of 3, 4, 6, 3 bottleneck units)."""
    shapes = {"conv0_weight": (64, 3, 7, 7), "bn0_gamma": (64,),
              "fc1_weight": (1000, 2048), "fc1_bias": (1000,)}
    cin = 64
    for stage, (units, width) in enumerate(
            zip((3, 4, 6, 3), (256, 512, 1024, 2048)), 1):
        for unit in range(1, units + 1):
            n = "stage%d_unit%d" % (stage, unit)
            shapes[n + "_conv1_weight"] = (width // 4, cin, 1, 1)
            shapes[n + "_conv2_weight"] = (width // 4, width // 4, 3, 3)
            shapes[n + "_conv3_weight"] = (width, width // 4, 1, 1)
            if unit == 1:
                shapes[n + "_sc_weight"] = (width, cin, 1, 1)
            cin = width
    return shapes


def hand_count_macs():
    """The same count, stage by stage, with the feature-map sizes written
    down: 112 after the stem, then 56, 28, 14, 7."""
    macs = 112 * 112 * 64 * 3 * 49
    cin, size_in = 64, 56
    for units, width, size in ((3, 256, 56), (4, 512, 28), (6, 1024, 14),
                               (3, 2048, 7)):
        mid = width // 4
        for unit in range(units):
            s1 = size_in if unit == 0 else size     # 1x1 before the stride
            macs += s1 * s1 * mid * cin
            macs += size * size * mid * mid * 9
            macs += size * size * width * mid
            if unit == 0:
                macs += size * size * width * cin
            cin = width
        size_in = size
    return macs + 2048 * 1000


def test_resnet50_flops_per_row():
    work = CELL.work("resnet")
    macs = work.forward_macs_per_row(resnet50_shapes(), (3, 224, 224))
    assert macs == hand_count_macs()
    assert macs == pytest.approx(4.1e9, rel=0.01)       # "about 4.1 GMAC"
    flops = work.train_flops_per_row(resnet50_shapes(), (3, 224, 224))
    assert flops == 6 * macs
    assert flops == pytest.approx(24.6e9, rel=0.01)


def test_resnet_tiny_basic_units():
    # the rehearsal network: 3x3 stem at full size, basic units, strides
    # on the first 3x3 of stages 2 and 3
    shapes = {"conv0_weight": (16, 3, 3, 3), "fc1_weight": (10, 64)}
    cin = 16
    for stage, width in enumerate((16, 32, 64), 1):
        n = "stage%d_unit1" % stage
        shapes[n + "_conv1_weight"] = (width, cin, 3, 3)
        shapes[n + "_conv2_weight"] = (width, width, 3, 3)
        shapes[n + "_sc_weight"] = (width, cin, 1, 1)
        cin = width
    hand = 16 * 16 * 16 * 27
    hand += 256 * (16 * 16 * 9 + 16 * 16 * 9 + 16 * 16)
    hand += 64 * (32 * 16 * 9 + 32 * 32 * 9 + 32 * 16)
    hand += 16 * (64 * 32 * 9 + 64 * 64 * 9 + 64 * 32)
    hand += 640
    assert CELL.work("resnet").forward_macs_per_row(
        shapes, (3, 16, 16)) == hand


def test_sgd_momentum_is_20_bytes_a_parameter():
    rule = CELL.work("sgd_momentum")
    assert rule.bytes_per_step(1) == 20
    n = CELL.work("resnet").parameter_count(
        {"a": (512, 512, 3, 3), "b": (64,)})
    assert n == 512 * 512 * 9 + 64
    peaks = {"hbm_bytes_per_s": 819e9}
    # 25.6 M parameters: 0.63 ms at the HBM rate
    assert rule.roofline_seconds(25.6e6, peaks) == pytest.approx(
        0.625e-3, rel=0.01)


def test_paged_decode_bytes_are_the_block_tables_kv():
    k = CELL.work("paged_decode_attention")
    # two live sequences of 100 and 300 tokens; 16 heads of 128 in bf16:
    # a token's K and V in one layer are 2 x 16 x 128 x 2 B = 8192 B
    got = k.bytes_per_layer_step([100, 300], 16, 16, 128, 2)
    assert got == 400 * 8192 + 2 * (2 * 16 * 128 * 2)
    # all 24 layers: 196608 B a token, the configuration's figure
    assert 24 * k.bytes_per_layer_step([1], 16, 16, 128, 2) \
        == 196608 + 24 * 8192
    assert k.flops_per_layer_step([400], 16, 128) == 4 * 400 * 16 * 128
    assert k.roofline_seconds(819e9, {"hbm_bytes_per_s": 819e9}) == 1.0
