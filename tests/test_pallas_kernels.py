"""Pallas hot-path burn-down: interpret-mode parity for the PR-17
kernels (flash prefill attention with fused page write, int8 im2col
conv) plus the kernel-contract lint and the warmed-dispatch compile
gate.

Every kernel under ops/pallas/ is pinned to its pure-lax twin
(PALLAS_KERNELS registry): the Pallas interpreter result must match
the twin — bitwise for integer math and page copies, allclose at
float32 round-off for online-softmax attention — and the off-TPU
default dispatch must BE the twin (so tier-1 CPU numerics never
change).
"""
import importlib.util
import os

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (registers nd ops)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.pallas.flash_attention import (  # noqa: E402
    _flash_fwd_xla, _flash_prefill_xla, flash_attention,
    flash_prefill_paged)
from mxnet_tpu.ops.pallas.int8_matmul import (  # noqa: E402
    _int8_conv_xla, int8_conv_im2col)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# flash attention (dense forward)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,d,causal", [
    (2, 4, 64, 32, True),
    (1, 2, 128, 16, False),
    (2, 3, 96, 8, True),
])
def test_flash_attention_interpret_matches_twin(b, h, s, d, causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    o = flash_attention(q, k, v, causal=causal, interpret=True)
    ref, _ = _flash_fwd_xla(q, k, v, causal, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash prefill attention + fused page write
# ---------------------------------------------------------------------------

def _prefill_case(seed, b, s, nh, kvh, hd, ps, num_pages):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, nh, hd).astype(np.float32))
    kg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    vg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(num_pages, ps, kvh, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(num_pages, ps, kvh, hd).astype(np.float32))
    n_pb = s // ps
    # distinct pages per row, leaving some pages untouched
    bt = jnp.asarray(
        np.arange(b * n_pb, dtype=np.int32).reshape(b, n_pb))
    return q, kg, vg, kp, vp, bt


@pytest.mark.parametrize("b,s,nh,kvh,hd,ps", [
    (2, 32, 4, 2, 16, 8),    # GQA, 4 pages/row
    (1, 16, 2, 2, 8, 16),    # MHA, single page/row
    (2, 24, 6, 3, 8, 8),     # 3 kv heads, non-pow2 bucket
])
def test_flash_prefill_interpret_matches_twin(b, s, nh, kvh, hd, ps):
    num_pages = 2 * b * (s // ps) + 3
    q, kg, vg, kp, vp, bt = _prefill_case(1, b, s, nh, kvh, hd, ps,
                                          num_pages)
    o, kp_n, vp_n = flash_prefill_paged(q, kg, vg, kp, vp, bt,
                                        interpret=True)
    ox, kpx, vpx = _flash_prefill_xla(q, kg, vg, kp, vp, bt)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ox),
                               rtol=2e-5, atol=2e-5)
    # the fused page-write epilogue is bitwise: pages are copied, not
    # recomputed
    np.testing.assert_array_equal(np.asarray(kp_n), np.asarray(kpx))
    np.testing.assert_array_equal(np.asarray(vp_n), np.asarray(vpx))
    # untouched pool pages are preserved via in->out aliasing
    touched = set(np.asarray(bt).ravel().tolist())
    for p in range(num_pages):
        if p not in touched:
            np.testing.assert_array_equal(np.asarray(kp_n[p]),
                                          np.asarray(kp[p]))


@pytest.mark.parametrize("hd", [16, 128])     # XLA scatter / DMA page write
@pytest.mark.parametrize("layer", [1, 2])
def test_flash_prefill_whole_pool_writes_its_layer_only(layer, hd):
    """The kernel takes the pool of every layer and the layer's index: it
    agrees with its twin, is bitwise the call over that layer's own 4-D
    pool, and every other layer comes back as it went in."""
    b, s, nh, kvh, ps = 2, 32, 4, 2, 8
    num_pages = 2 * b * (s // ps) + 3
    q, kg, vg, _kp, _vp, bt = _prefill_case(4, b, s, nh, kvh, hd, ps,
                                            num_pages)
    rng = np.random.RandomState(40 + layer)
    kp, vp = (jnp.asarray(rng.randn(3, num_pages, ps, kvh, hd)
                          .astype(np.float32)) for _ in range(2))
    o, kp_n, vp_n = flash_prefill_paged(q, kg, vg, kp, vp, bt,
                                        interpret=True, layer=layer)
    ox, kpx, vpx = _flash_prefill_xla(q, kg, vg, kp, vp, bt, layer=layer)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ox),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kp_n), np.asarray(kpx))
    np.testing.assert_array_equal(np.asarray(vp_n), np.asarray(vpx))
    o1, kp1, vp1 = flash_prefill_paged(q, kg, vg, kp[layer], vp[layer], bt,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o1))
    np.testing.assert_array_equal(np.asarray(kp_n[layer]), np.asarray(kp1))
    np.testing.assert_array_equal(np.asarray(vp_n[layer]), np.asarray(vp1))
    assert not np.array_equal(np.asarray(kp_n[layer]), np.asarray(kp[layer]))
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(np.asarray(kp_n[other]),
                                      np.asarray(kp[other]))
        np.testing.assert_array_equal(np.asarray(vp_n[other]),
                                      np.asarray(vp[other]))


@pytest.mark.parametrize("ndim,layer", [(5, None), (5, 3), (5, -1), (4, 0)])
def test_paged_kernels_refuse_a_pool_without_its_layer(ndim, layer):
    """A whole pool needs a layer it has; one layer's pool takes none."""
    from mxnet_tpu.ops.pallas.flash_attention import paged_decode_attention
    q, kg, vg, kp, vp, bt = _prefill_case(5, 1, 16, 2, 2, 8, 8, 4)
    if ndim == 5:
        kp, vp = jnp.stack([kp] * 3), jnp.stack([vp] * 3)
    with pytest.raises(ValueError, match="layer"):
        flash_prefill_paged(q, kg, vg, kp, vp, bt, interpret=True,
                            layer=layer)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q[:, 0].reshape(1, 2, 1, 8), kp, vp, bt,
                               jnp.asarray([3], jnp.int32), interpret=True,
                               layer=layer)


def test_flash_prefill_default_dispatch_is_twin_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU dispatch contract")
    q, kg, vg, kp, vp, bt = _prefill_case(2, 2, 32, 4, 2, 16, 8, 11)
    o, kp_n, vp_n = flash_prefill_paged(q, kg, vg, kp, vp, bt)
    ox, kpx, vpx = _flash_prefill_xla(q, kg, vg, kp, vp, bt)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ox))
    np.testing.assert_array_equal(np.asarray(kp_n), np.asarray(kpx))
    np.testing.assert_array_equal(np.asarray(vp_n), np.asarray(vpx))


def test_flash_prefill_null_page_warmup_row():
    """The decode warmup batch maps every page slot to page 0: both the
    kernel DMA (sequential over ki then j) and the twin's scatter are
    last-write-wins, so page 0 must hold the LAST position block and
    the pools must still agree bitwise."""
    b, s, nh, kvh, hd, ps = 1, 32, 4, 2, 16, 8
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, s, nh, hd).astype(np.float32))
    kg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    vg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(6, ps, kvh, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(6, ps, kvh, hd).astype(np.float32))
    bt = jnp.zeros((b, s // ps), jnp.int32)
    o, kp_n, vp_n = flash_prefill_paged(q, kg, vg, kp, vp, bt,
                                        interpret=True)
    ox, kpx, vpx = _flash_prefill_xla(q, kg, vg, kp, vp, bt)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ox),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kp_n), np.asarray(kpx))
    np.testing.assert_array_equal(np.asarray(vp_n), np.asarray(vpx))
    np.testing.assert_array_equal(np.asarray(kp_n[0]),
                                  np.asarray(kg[0, -ps:]))
    # pages beyond slot 0 keep their prior contents
    np.testing.assert_array_equal(np.asarray(kp_n[1:]),
                                  np.asarray(kp[1:]))


def test_flash_prefill_validations():
    q, kg, vg, kp, vp, bt = _prefill_case(4, 1, 16, 2, 2, 8, 16, 5)
    with pytest.raises(ValueError, match="not a multiple of page_size"):
        flash_prefill_paged(q[:, :12], kg[:, :12], vg[:, :12],
                            kp, vp, bt)
    with pytest.raises(ValueError, match="pages/row"):
        flash_prefill_paged(q, kg, vg, kp, vp, bt[:, :0])


# ---------------------------------------------------------------------------
# int8 im2col conv
# ---------------------------------------------------------------------------

def _conv_case(seed, b, cin, hw, cout, k, zero_channel=False):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(
        rng.randint(-127, 128, (b, cin, hw, hw)).astype(np.int8))
    wq = jnp.asarray(
        rng.randint(-127, 128, (cout,) + k).astype(np.int8))
    scale = rng.rand(cout).astype(np.float32) * 0.01 + 1e-4
    if zero_channel:
        scale[1] = 0.0
    return q, wq, jnp.asarray(scale)


@pytest.mark.parametrize(
    "cin,hw,cout,kh,stride,dilate,pad,groups,zero_ch", [
        (3, 8, 4, 3, (1, 1), (1, 1), (0, 0), 1, False),
        (4, 9, 6, 3, (2, 2), (2, 2), (1, 1), 2, False),
        (3, 7, 4, 1, (1, 1), (1, 1), (0, 0), 1, False),
        (2, 8, 4, 3, (1, 1), (1, 1), (1, 1), 1, True),
    ])
def test_int8_conv_im2col_interpret_bitwise(cin, hw, cout, kh, stride,
                                            dilate, pad, groups,
                                            zero_ch):
    q, wq, scale = _conv_case(11, 2, cin, hw, cout,
                              (cin // groups, kh, kh), zero_ch)
    out = int8_conv_im2col(q, wq, scale, stride, dilate, pad,
                           num_group=groups, interpret=True)
    ref = _int8_conv_xla(q, wq, scale, stride, dilate, pad, groups)
    # int32 accumulation + one f32 rescale on both routes -> bitwise
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    if zero_ch:
        np.testing.assert_array_equal(np.asarray(out[:, 1]), 0.0)


def test_quantized_conv_int8_op_im2col_route(monkeypatch):
    """MXNET_INT8_CONV_IM2COL=1 swaps _contrib_quantized_conv_int8 onto
    the im2col-MXU route; off-TPU both routes are exact int32 conv +
    per-channel rescale, so the op output must be bitwise identical."""
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.quantize.ptq import _per_channel_quantize
    rng = np.random.RandomState(12)
    x = rng.randn(2, 3, 8, 8).astype(np.float32)
    w = rng.randn(4, 3, 3, 3).astype(np.float32) * 0.3
    bias = rng.randn(4).astype(np.float32)
    wq, ws = _per_channel_quantize(w)
    op = get_op("_contrib_quantized_conv_int8").fn
    kw = dict(kernel=(3, 3), num_filter=4,
              act_scale=float(127.0 / np.abs(x).max()))
    monkeypatch.delenv("MXNET_INT8_CONV_IM2COL", raising=False)
    ref = np.asarray(op(jnp.asarray(x), jnp.asarray(wq),
                        jnp.asarray(ws), jnp.asarray(bias), **kw))
    monkeypatch.setenv("MXNET_INT8_CONV_IM2COL", "1")
    out = np.asarray(op(jnp.asarray(x), jnp.asarray(wq),
                        jnp.asarray(ws), jnp.asarray(bias), **kw))
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# kernel contract lint + warmed-dispatch gate + prefill variant tag
# ---------------------------------------------------------------------------

def test_kernel_contract_lint():
    spec = importlib.util.spec_from_file_location(
        "check_pallas_contracts",
        os.path.join(ROOT, "tools", "check_pallas_contracts.py"))
    modl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(modl)
    drift = modl.check()
    assert all(not v for v in drift.values()), drift


def _warmed_dispatch_case(kernel):
    """(production dispatch, its arguments) of one burned-down kernel."""
    if kernel == "flash_prefill_paged":
        return flash_prefill_paged, _prefill_case(3, 2, 32, 4, 2, 16, 8, 11)
    q, wq, scale = _conv_case(15, 2, 3, 8, 4, (3, 3, 3))
    return (lambda q, wq, scale: int8_conv_im2col(
        q, wq, scale, (1, 1), (1, 1), (1, 1), 1), (q, wq, scale))


@pytest.mark.parametrize("kernel", [
    "flash_prefill_paged", "int8_conv_im2col"])
def test_warmed_pallas_dispatch_compiles_nothing(kernel):
    """A kernel's production dispatch, jitted and called once, leaks no
    counted backend compile into the calls that follow."""
    from mxnet_tpu import telemetry as tm
    fn, args = _warmed_dispatch_case(kernel)
    run = jax.jit(fn)
    tm.enable(True)                      # installs the compile listener
    cold = tm.snapshot()["backend_compile_total"]
    jax.block_until_ready(run(*args))    # compile + execute = warm
    compiles0 = tm.snapshot()["backend_compile_total"]
    assert compiles0 > cold              # the counter sees this program
    for _ in range(3):
        jax.block_until_ready(run(*args))
    assert tm.snapshot()["backend_compile_total"] == compiles0


def test_prefill_variant_tag_in_program_key():
    from mxnet_tpu.serve.decode import _prefill_variant
    from mxnet_tpu.programs import ProgramKey
    if jax.default_backend() != "tpu":
        assert _prefill_variant() == "xla-prefill"
    tagged = ProgramKey("decode_prefill", "g",
                        {"bucket": 128, "kernel": _prefill_variant()})
    untagged = ProgramKey("decode_prefill", "g", {"bucket": 128})
    assert tagged.fingerprint != untagged.fingerprint
