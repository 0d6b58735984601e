"""Qwen3-Next's block — Gated DeltaNet linear-attention layers whose
recurrent state lives in a state row beside the paged K/V of every fourth
layer, gated attention with partial rotary and per-head q/k norms, a
zero-centred norm gain, a sigmoid gate on the shared expert and ten of many
experts a token with a device that holds only SOME of them — through the
framework's normal paths at a small size, in float32, against the
benchmark's plain reference (``bench/reference/qwen3_next_80b_a3b.py``: no
cache, no kernels, the recurrence token by token).
"""
import functools
import importlib.util
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from mxnet_tpu.ops.pallas.gated_delta import (  # noqa: E402
    CHUNK, _gdn_chunk_xla, _gdn_recurrent_xla, gdn_chunk_prefill,
    gdn_recurrent_step)
from mxnet_tpu.parallel import transformer as T  # noqa: E402
from mxnet_tpu.parallel.moe import top_k_routing  # noqa: E402
from mxnet_tpu.parallel.transformer import (  # noqa: E402
    LinearStateCache, TransformerConfig, cache_pools, init_kv_cache,
    init_kv_pages, init_transformer_params, kv_layer_kinds,
    make_transformer_train_step, paged_cache, transformer_decode_step,
    transformer_forward_single, transformer_prefill_paged)
from mxnet_tpu.serve import DecodeConfig, DecodeEngine  # noqa: E402
from mxnet_tpu.serve.batching import pick_bucket  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CONTEXT = 8, 100
TOL = 2e-5
# two periods [linear, linear, linear, full]
MODEL = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=16, n_layers=8, d_ff=32, max_len=128, num_experts=16,
             moe_top_k=4, pos_type="rope", rope_base=1e7, norm="rmsnorm",
             norm_eps=1e-6, tie_embeddings=False, moe_router="topk",
             gate_act="silu", moe_shared_width=32, moe_shared_gate=True,
             moe_local_experts=(0, 8), rotary_share=0.25, qk_norm=True,
             attn_gate=True, norm_zero_centered=True,
             linear_layout=(1, 1, 1, 0) * 2, linear_key_heads=2,
             linear_value_heads=4, linear_key_dim=16, linear_value_dim=16,
             linear_conv_width=4)
NEW_FIELDS = dict(linear_layout=(1, 0, 1, 0), linear_key_heads=2,
                  linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
                  linear_conv_width=4, rotary_share=0.5, qk_norm=True,
                  attn_gate=True, norm_zero_centered=True,
                  moe_shared_gate=True)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_qwen3_next", os.path.join(
            ROOT, "bench", "reference", "qwen3_next_80b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))


def _draw(model, seed=3):
    """The reference's tree drawn as the benchmark draws it (its
    ``init_std`` a kind), float32."""
    rng = np.random.RandomState(seed)

    def leaf(spec):
        shape, kind = spec
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        return jnp.asarray(rng.randn(*shape) * REF.init_std(kind, model),
                           jnp.float32)

    return jax.tree_util.tree_map(
        leaf, REF.param_tree(model),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))


@pytest.fixture(scope="module")
def model():
    return _draw(MODEL), TransformerConfig(**MODEL)


@pytest.fixture(scope="module")
def sequence(model):
    params, _cfg = model
    tokens = np.random.RandomState(0).randint(0, 256, CONTEXT)
    logits, experts = REF.forward(params, tokens, MODEL)
    return tokens, np.asarray(logits), np.asarray(experts)


def _state_cache(cfg, rows, pages_per_seq, state_rows=None):
    """Pools for ``rows`` sequences, page tables and state rows 1 ..."""
    kp, vp = init_kv_pages(
        cfg, (rows * pages_per_seq + 1, (state_rows or rows) + 1), PAGE)
    table = 1 + np.arange(rows * pages_per_seq, dtype=np.int32)
    return kp, vp, jnp.asarray(table.reshape(rows, -1))


def _linear_layer(params, li=0):
    return jax.tree_util.tree_map(lambda p: p[0, li],
                                  params["linear_layers"])


def _rule_inputs(seed, b, s, kh, vh, dk, dv, lengths=None):
    rng = np.random.RandomState(seed)
    unit = lambda t: t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.randn(b, s, kh, dk)) * dk ** -0.5
    k = unit(rng.randn(b, s, kh, dk))
    v = rng.randn(b, s, vh, dv)
    g = -np.exp(rng.randn(b, s, vh) * 2) * 0.7
    beta = 1 / (1 + np.exp(-rng.randn(b, s, vh)))
    real = np.ones((b, s), bool) if lengths is None else \
        np.arange(s)[None, :] < np.asarray(lengths)[:, None]
    g, beta = (np.where(real[..., None], t, 0.0) for t in (g, beta))
    return tuple(jnp.asarray(t, jnp.float32)
                 for t in (q, k, v, g, beta)) + (real,)


def _token_by_token(q, k, v, g, beta):
    """The recurrent twin over a sequence: (o (b, s, vh, dv), state)."""
    b, s, vh, dv = v.shape
    pool = jnp.zeros((1, b + 1, vh, q.shape[-1], dv), jnp.float32)
    rows = jnp.arange(1, b + 1)
    outs = []
    for t in range(s):
        o, pool = _gdn_recurrent_xla(q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t], pool, rows, 0)
        outs.append(o)
    return np.stack(outs, 1), np.asarray(pool[0, 1:])


# -- the model against the reference ----------------------------------------

def test_reference_tree_is_the_programs(model):
    params, cfg = model
    built, _ = init_transformer_params(cfg, _mesh(), seed=1)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(built) == shapes(params)
    assert set(built) == {"embed", "head", "lnf_g", "linear_layers",
                          "layers"}
    assert kv_layer_kinds(cfg) == ("linear", "linear", "linear", "full") * 2
    assert built["layers"]["wq"].shape == (1, 2, 64, 4 * 2 * 16)
    assert built["linear_layers"]["gdn_qkvz"].shape == (
        1, 6, 64, 2 * 2 * 16 + 2 * 4 * 16)
    assert built["linear_layers"]["we_gate"].shape == (1, 6, 8, 64, 32)
    assert built["layers"]["gate"].shape == (1, 2, 64, 16)    # 16 scored
    assert "wq" not in built["linear_layers"] \
        and "gdn_out" not in built["layers"]


def test_forward_single_matches_reference(model, sequence):
    params, cfg = model
    tokens, want, want_experts = sequence
    got, stats = transformer_forward_single(
        params, jnp.asarray(tokens[None]), cfg, with_stats=True)
    assert np.abs(np.asarray(got)[0] - want).max() <= TOL
    assert stats["moe_experts"].shape == (8, cfg.moe_top_k, CONTEXT)
    assert np.array_equal(
        np.sort(np.asarray(stats["moe_experts"]).transpose(0, 2, 1), -1),
        np.sort(want_experts, -1))
    assert want_experts.max() > 7            # some are not held here


@pytest.mark.parametrize("change", [
    {"rotary_share": 1.0}, {"norm_topk_prob": False}],
    ids=lambda c: sorted(c)[0])
def test_the_comparison_sees_a_wrong_rule(model, sequence, change):
    """The controls the benchmark's traced run reads: a rotation over the
    whole head, and top-k weights left un-renormalised, move the
    reference's logits by far more than ``TOL``."""
    params, _cfg = model
    tokens, want, _experts = sequence
    wrong, _ = REF.forward(params, tokens, dict(MODEL, **change))
    assert np.abs(np.asarray(wrong) - want).max() > 100 * TOL


def test_prefill_then_decode_through_rows_and_pages_matches_reference(
        model, sequence):
    """Two rows in one bucket of 64 — a prompt that ends mid-chunk and
    mid-page (37) and one shorter than the convolution is wide (2) — then
    twenty ragged decode steps over pages and state rows."""
    params, cfg = model
    tokens, want, _experts = sequence
    lengths = np.array([37, 2])
    kp, vp, table = _state_cache(cfg, 2, 16)
    rows = jnp.asarray([[1], [2]])
    padded = np.zeros((2, 64), np.int32)
    for r, n in enumerate(lengths):
        padded[r, :n] = tokens[:n]
    cache = paged_cache(kp, vp, (table[:, :64 // PAGE], rows), PAGE, cfg)
    assert isinstance(cache, LinearStateCache)
    logits, cache = transformer_prefill_paged(
        params, cache, jnp.asarray(padded), jnp.asarray(lengths), cfg)
    for r, n in enumerate(lengths):
        assert np.abs(np.asarray(logits)[r] - want[n - 1]).max() <= TOL
    kp, vp = cache_pools(cache)
    assert kp[0].shape[0] == 2 and kp[1].shape == (6, 3, 4, 16, 16)
    assert vp[1].shape == (6, 3, 3 * (2 * 2 * 16 + 4 * 16))
    assert not np.asarray(kp[1][:, 0]).any()      # the null row untouched
    pos = lengths.copy()
    for _ in range(20):
        cache = paged_cache(kp, vp, (table, rows), PAGE, cfg)
        logits, cache = transformer_decode_step(
            params, cache, jnp.asarray(tokens[pos]), jnp.asarray(pos), cfg)
        kp, vp = cache_pools(cache)
        for r in range(2):
            assert np.abs(np.asarray(logits)[r] - want[pos[r]]).max() <= TOL
        pos = pos + 1


# -- the rule: chunked == token by token, kernels == twins --------------------

@pytest.mark.parametrize("s,lengths", [
    (150, [150, 97]),       # ends mid-chunk; a row padded past its length
    (64, [64, 2]),          # a whole chunk; a prompt shorter than the conv
    (256, [130, 256]),      # padded to its bucket, two chunks of padding
], ids=["mid_chunk", "shorter_than_conv", "padded_to_bucket"])
def test_chunked_rule_is_the_token_by_token_rule(s, lengths):
    q, k, v, g, beta, real = _rule_inputs(1, 2, s, 2, 4, 16, 16, lengths)
    o_c, state_c = _gdn_chunk_xla(q, k, v, g, beta)
    o_r, state_r = _token_by_token(q, k, v, g, beta)
    assert np.abs(np.asarray(o_c) - o_r)[real].max() <= 1e-5
    assert np.abs(np.asarray(state_c) - state_r).max() <= 1e-5
    # the state at the TRUE length: the padding left it alone
    for r, n in enumerate(lengths):
        _o, state_n = _token_by_token(*(t[r:r + 1, :n]
                                        for t in (q, k, v, g, beta)))
        assert np.abs(np.asarray(state_c)[r] - state_n[0]).max() <= 1e-5


@pytest.mark.parametrize("s", [64, 200])
def test_chunk_kernel_matches_its_twin(s):
    q, k, v, g, beta, real = _rule_inputs(2, 1, s, 1, 2, 128, 128, [s - 9])
    want_o, want_state = _gdn_chunk_xla(q, k, v, g, beta)
    got_o, got_state = gdn_chunk_prefill(q, k, v, g, beta, interpret=True)
    assert got_o.shape == (1, s, 2, 128) and CHUNK == 64
    assert np.abs(np.asarray(got_o) - np.asarray(want_o))[real].max() <= 1e-5
    assert np.abs(np.asarray(got_state)
                  - np.asarray(want_state)).max() <= 1e-5
    # off the TPU the default dispatch is the twin
    assert np.array_equal(np.asarray(gdn_chunk_prefill(q, k, v, g, beta)[1]),
                          np.asarray(want_state))


@pytest.mark.parametrize("layer", [0, 1])
def test_recurrent_kernel_matches_its_twin_in_place(layer):
    q, k, v, g, beta, _real = _rule_inputs(3, 3, 1, 1, 8, 128, 128)
    pool = jnp.asarray(np.random.RandomState(4).randn(2, 5, 8, 128, 128),
                       jnp.float32)
    rows = jnp.asarray([3, 1, 4])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], pool, rows)
    want_o, want_pool = _gdn_recurrent_xla(*args, layer)
    got_o, got_pool = gdn_recurrent_step(*args, layer=layer, interpret=True)
    assert np.abs(np.asarray(got_o) - np.asarray(want_o)).max() <= 1e-5
    assert np.abs(np.asarray(got_pool) - np.asarray(want_pool)).max() <= 1e-5
    # only the three rows of that layer moved
    same = np.asarray(got_pool) == np.asarray(pool)
    assert same[1 - layer].all() and same[layer, [0, 2]].all()
    assert not same[layer, [1, 3, 4]].all(axis=(1, 2, 3)).any()
    assert np.array_equal(np.asarray(gdn_recurrent_step(
        *args, layer=layer)[0]), np.asarray(want_o))


# -- one test a mechanism ------------------------------------------------------

def _gdn_parts(model, seed=7, s=12):
    params, cfg = model
    lp = _linear_layer(params, 1)
    h = jnp.asarray(np.random.RandomState(seed).randn(2, s, 64), jnp.float32)
    return cfg, lp, h, T._gdn_inputs(cfg, lp, h)


def test_decay_gate(model):
    """g = -exp(A_log) softplus(a + dt_bias), float32, a value head."""
    cfg, lp, h, (_q, _k, _v, _z, g, _beta, _tail) = _gdn_parts(model)
    a = (np.asarray(h) @ np.asarray(lp["gdn_ba"]))[..., 4:]
    want = -np.exp(np.asarray(lp["gdn_a_log"])) * np.log1p(
        np.exp(a + np.asarray(lp["gdn_dt_bias"])))
    assert g.shape == (2, 12, 4) and g.dtype == jnp.float32
    assert np.abs(np.asarray(g) - want).max() <= 1e-5
    assert (np.asarray(g) < 0).all()
    # the drawn decays span memories of one token to many
    assert np.asarray(g).min() < -1.0 and np.asarray(g).max() > -0.5


def test_beta_is_the_sigmoid_of_b(model):
    cfg, lp, h, (_q, _k, _v, _z, _g, beta, _tail) = _gdn_parts(model)
    b = (np.asarray(h) @ np.asarray(lp["gdn_ba"]))[..., :4]
    assert np.abs(np.asarray(beta) - 1 / (1 + np.exp(-b))).max() <= 1e-6


def test_l2_norms_and_the_query_scale(model):
    cfg, lp, h, (q, k, _v, _z, _g, _beta, _tail) = _gdn_parts(model)
    assert q.shape == k.shape == (2, 12, 2, 16)
    assert np.abs(np.linalg.norm(np.asarray(k), axis=-1) - 1).max() <= 1e-4
    assert np.abs(np.linalg.norm(np.asarray(q), axis=-1)
                  - 16 ** -0.5).max() <= 1e-4


def test_padding_leaves_the_gates_closed(model):
    params, cfg = model
    lp = _linear_layer(params)
    h = jnp.asarray(np.random.RandomState(2).randn(2, 8, 64), jnp.float32)
    *_rest, g, beta, _tail = T._gdn_inputs(cfg, lp, h, None,
                                           jnp.asarray([8, 3]))
    assert not np.asarray(g)[1, 3:].any() and not np.asarray(beta)[1, 3:].any()
    assert np.asarray(beta)[1, :3].all() and np.asarray(g)[0].all()


def test_conv_tail_is_handed_from_prefill_to_decode(model):
    """The convolution over a prefix and then token by token with the
    tail it left is the convolution over the whole sequence; a prompt
    shorter than the tail pads it with zeros on the left."""
    params, cfg = model
    lp = _linear_layer(params, 2)
    h = jnp.asarray(np.random.RandomState(9).randn(1, 10, 64), jnp.float32)
    whole = T._gdn_inputs(cfg, lp, h)
    for n in (2, 6):
        # a bucket of 8, the prompt's real length n
        part = T._gdn_inputs(cfg, lp, h[:, :8], None, jnp.asarray([n]))
        tail = part[-1]
        assert tail.shape == (1, 3, 2 * 2 * 16 + 4 * 16)
        if n == 2:
            assert not np.asarray(tail)[0, 0].any()       # left of the start
        for t in range(n, 10):
            q, k, v, z, _g, _b, tail = T._gdn_inputs(
                cfg, lp, h[:, t:t + 1], tail)
            for got, want in zip((q, k, v, z), whole[:4]):
                assert np.abs(np.asarray(got)[:, 0]
                              - np.asarray(want)[:, t]).max() <= 1e-6


def test_gated_norm_has_a_plain_gain(model):
    params, cfg = model
    lp = dict(_linear_layer(params),
              gdn_norm_g=jnp.asarray(np.linspace(0.5, 1.5, 16), jnp.float32))
    rng = np.random.RandomState(1)
    o, z = rng.randn(5, 4, 16), rng.randn(5, 4, 16)
    want = (o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6)
            * np.linspace(0.5, 1.5, 16) * z / (1 + np.exp(-z))) \
        .reshape(5, 64) @ np.asarray(lp["gdn_out"])
    got = T._gdn_out(cfg, lp, jnp.asarray(o, jnp.float32),
                     jnp.asarray(z, jnp.float32))
    assert np.abs(np.asarray(got) - want).max() <= 1e-5


def _full_layer(params, li=0):
    return jax.tree_util.tree_map(lambda p: p[0, li], params["layers"])


def test_attention_output_gate(model):
    """A head's columns of wq are [query ; gate]; the attention's output
    is times sigmoid(gate) before the output map."""
    params, cfg = model
    lp = _full_layer(params)
    h = jnp.asarray(np.random.RandomState(5).randn(3, 64), jnp.float32)
    plain = TransformerConfig(**dict(MODEL, qk_norm=False))
    q, _k, _v, gate = T._attn_qkv(plain, lp, h, jnp.zeros(3), False)
    cols = (np.asarray(h) @ np.asarray(lp["wq"])).reshape(3, 4, 32)
    assert np.abs(np.asarray(q) - cols[..., :16]).max() <= 1e-6
    assert np.abs(np.asarray(gate) - cols[..., 16:].reshape(3, 64)
                  ).max() <= 1e-6
    o = jnp.asarray(np.random.RandomState(6).randn(3, 64), jnp.float32)
    want = (np.asarray(o) / (1 + np.exp(-np.asarray(gate)))) \
        @ np.asarray(lp["wo"])
    assert np.abs(np.asarray(T._attn_out(lp, o, gate)) - want).max() <= 1e-5


def test_partial_rotary_turns_the_first_quarter(model):
    _params, cfg = model
    t = jnp.asarray(np.random.RandomState(8).randn(5, 2, 16), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 4000])
    got = np.asarray(T._rotate(cfg, t, pos))
    assert np.array_equal(got[..., 4:], np.asarray(t)[..., 4:])
    assert np.array_equal(got[0], np.asarray(t)[0])          # position 0
    ang = np.asarray(pos)[:, None] * 1e7 ** (-np.arange(2) / 2.0)
    a, b = np.asarray(t)[..., :2], np.asarray(t)[..., 2:4]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    assert np.abs(got[..., :2] - (a * cos - b * sin)).max() <= 1e-5
    assert np.abs(got[..., 2:4] - (a * sin + b * cos)).max() <= 1e-5


def test_q_and_k_are_normalised_a_head(model):
    params, cfg = model
    lp = _full_layer(params, 1)
    h = jnp.asarray(np.random.RandomState(5).randn(3, 64), jnp.float32)
    q, k, _v, _gate = T._attn_qkv(cfg, lp, h, jnp.zeros(3), False)
    raw = (np.asarray(h) @ np.asarray(lp["wk"])).reshape(3, 2, 16)
    want = raw / np.sqrt((raw * raw).mean(-1, keepdims=True) + 1e-6) \
        * (1 + np.asarray(lp["k_norm_g"]))
    assert np.abs(np.asarray(k) - want).max() <= 1e-5
    rms = np.sqrt((np.asarray(q) ** 2).mean(-1))
    assert np.abs(rms - 1).max() < 0.2          # unit but for the gain


def test_zero_centred_gain(model):
    _params, cfg = model
    x = jnp.asarray(np.random.RandomState(3).randn(4, 64) * 3, jnp.float32)
    unit = np.asarray(x) / np.sqrt(
        (np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)
    zero = {"n_g": jnp.zeros(64)}
    assert np.abs(np.asarray(T._norm(cfg, zero, "n", x)) - unit).max() <= 1e-5
    w = np.linspace(-0.5, 0.5, 64).astype(np.float32)
    got = T._norm(cfg, {"n_g": jnp.asarray(w)}, "n", x)
    assert np.abs(np.asarray(got) - unit * (1 + w)).max() <= 1e-5
    plain = TransformerConfig(**dict(MODEL, norm_zero_centered=False))
    assert np.abs(np.asarray(T._norm(plain, {"n_g": jnp.asarray(w)}, "n", x))
                  - unit * w).max() <= 1e-5


def test_shared_expert_is_gated_by_a_sigmoid(model):
    params, cfg = model
    lp = _full_layer(params)
    stacks = {n: params["layers"][n] for n in ("we_gate", "we_up",
                                               "we_down")}
    h = jnp.asarray(np.random.RandomState(4).randn(6, 64), jnp.float32)
    got, _stats = T._ffn(cfg, lp, h, h, stacks, (0, 0))
    ungated = TransformerConfig(**dict(MODEL, moe_shared_gate=False))
    plain, _stats = T._ffn(ungated, lp, h, h, stacks, (0, 0))
    shared = np.asarray(T._gated_ffn(cfg, lp, "ws_", h))
    sig = 1 / (1 + np.exp(-(np.asarray(h) @ np.asarray(lp["ws_sigmoid"]))))
    assert sig.shape == (6, 1)
    assert np.abs((np.asarray(plain) - np.asarray(got))
                  - shared * (1 - sig)).max() <= 1e-5
    assert np.abs(shared * (1 - sig)).max() > 1e-3


def test_top_k_weights_are_renormalised():
    logits = jnp.asarray(np.random.RandomState(2).randn(7, 32), jnp.float32)
    experts, w = top_k_routing(logits, 10)
    prob = np.asarray(jax.nn.softmax(logits, -1))
    top = np.sort(prob, -1)[:, ::-1][:, :10]
    assert np.abs(np.asarray(w) - top / top.sum(-1, keepdims=True)
                  ).max() <= 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1).max() <= 1e-6
    assert np.array_equal(np.asarray(experts),
                          np.argsort(-prob, -1)[:, :10])


@pytest.mark.parametrize("shares", [2, 8])
def test_the_shares_partial_outputs_sum_to_the_uncut_layer(model, shares):
    """Every device computes its own experts' part plus the gated shared
    expert; the parts, the shared expert counted once, are the uncut
    layer."""
    params, _cfg = model
    held = 16 // shares
    rng = np.random.RandomState(11)
    lp = _full_layer(params)
    full = {name: jnp.asarray(rng.randn(1, 1, 16, *lp[name].shape[1:])
                              * 0.05, jnp.float32)
            for name in ("we_gate", "we_up", "we_down")}
    h = jnp.asarray(rng.randn(24, 64), jnp.float32)

    def layer(first, count):
        cfg = TransformerConfig(**dict(MODEL,
                                       moe_local_experts=(first, count)))
        stacks = {n: w[:, :, first:first + count] for n, w in full.items()}
        out, (experts, active) = T._ffn(cfg, lp, h, h, stacks, (0, 0))
        return np.asarray(out), np.asarray(experts), int(active)

    whole, experts, _active = layer(0, 16)
    none = TransformerConfig(**dict(MODEL, moe_local_experts=(0, 1)))
    # what every device computes alike: the gated shared expert
    shared = np.asarray(T._ffn(none, lp, h, h, {
        n: jnp.zeros_like(w[:, :, :1]) for n, w in full.items()},
        (0, 0))[0])
    total, touched = shared.copy(), 0
    for i in range(shares):
        part, part_experts, active = layer(i * held, held)
        assert np.array_equal(part_experts, experts)   # all 16 are scored
        total += part - shared
        touched += active
    assert np.abs(total - whole).max() <= 1e-5
    assert touched == len(np.unique(experts))
    assert np.abs(whole - shared).max() > 1e-2          # the routed part


# -- the engine ----------------------------------------------------------------

def _unbatched(params, cfg, dcfg, prompt, new):
    """Per-request greedy decode through rows and pages, b = 1, the
    prompt in the engine's own bucket."""
    bucket = pick_bucket(len(prompt), dcfg.prefill_buckets)
    kp, vp, table = _state_cache(cfg, 1, dcfg.pages_per_seq)
    rows = jnp.asarray([[1]])
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    cache = paged_cache(kp, vp, (table[:, :bucket // PAGE], rows), PAGE, cfg)
    logits, cache = transformer_prefill_paged(
        params, cache, jnp.asarray(padded), jnp.asarray([len(prompt)]), cfg)
    out, pos = [int(jnp.argmax(logits[0]))], len(prompt)
    kp, vp = cache_pools(cache)
    while len(out) < new:
        logits, kp, vp = _one_step(cfg)(params, kp, vp, table, rows,
                                        jnp.asarray(out[-1:], jnp.int32),
                                        jnp.asarray([pos]))
        out.append(int(jnp.argmax(logits[0])))
        pos += 1
    return out


@functools.lru_cache(None)
def _one_step_of(cfg_repr):
    cfg = TransformerConfig(**MODEL)
    assert repr(cfg) == cfg_repr

    @jax.jit
    def step(params, kp, vp, table, rows, token, pos):
        cache = paged_cache(kp, vp, (table, rows), PAGE, cfg)
        logits, cache = transformer_decode_step(params, cache, token, pos,
                                                cfg)
        return (logits,) + cache_pools(cache)

    return step


def _one_step(cfg):
    return _one_step_of(repr(cfg))


def test_engine_serves_state_rows_bitwise_and_as_the_reference(model):
    """Batched continuous decode of a linear model == per-request decode,
    token for token; each stream is the reference's greedy choice; rows
    and pages come back; the spans count the linear layers' work."""
    from mxnet_tpu import telemetry as tm, tracing as tr
    params, cfg = model
    dcfg = DecodeConfig(slots=4, page_size=PAGE, num_pages=40,
                        max_context=64, queue_depth=16, max_new_tokens=24,
                        default_timeout_ms=120000)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        state, conv = eng._k_pages[1], eng._v_pages[1]
        assert state.shape == (6, 5, 4, 16, 16) \
            and state.dtype == jnp.float32
        assert conv.shape == (6, 5, 3 * 128)
        assert eng._k_pages[0].shape == (2, 40, PAGE, 2, 16)  # full layers
        assert eng.program_count() == 4 + 3
        free = tm.gauge("decode/state_rows_free")
        assert free.value == 4 == eng._state_rows.capacity  # a row a slot
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 256, n).tolist(), new) for n, new in
                [(3, 6), (12, 4), (5, 10), (2, 8), (33, 12), (1, 7)]]
        compiles0 = tm.snapshot()["backend_compile_total"]
        sessions = [eng.submit(p, new) for p, new in reqs]
        # pages for a request's positions, not for its bucket; a queued
        # request holds pages and no state row
        assert len(sessions[4].page_ids) == -(-45 // PAGE)
        outs = [s.result() for s in sessions]
        assert tm.snapshot()["backend_compile_total"] == compiles0
        assert eng._pool.used_pages == 0 \
            and eng._state_rows.used_pages == 0
        assert free.value == 4
    finally:
        eng.close()
    for (prompt, new), out, sess in zip(reqs, outs, sessions):
        assert out == _unbatched(params, cfg, dcfg, prompt, new)
        logits, experts = REF.forward(params, np.asarray(prompt + out),
                                      MODEL)
        rows = np.asarray(logits)[len(prompt) - 1:len(prompt) - 1 + new]
        gap = rows.max(-1) - rows[np.arange(new), np.asarray(out)]
        assert gap.max() <= TOL
        served = np.concatenate(sess.expert_choices, axis=1)
        assert served.shape[0] == 8               # every layer routes
        assert np.array_equal(np.sort(served, -1),
                              np.sort(np.asarray(experts)[:, :-1], -1))
    steps = [r["attrs"] for r in tr.span_log() if r["name"] == "decode.step"
             and "linear_rows" in r["attrs"]]
    assert steps and all(a["linear_rows"] % 6 == 0 for a in steps)
    assert max(a["linear_rows"] for a in steps) <= 4 * 6
    prefill = [r["attrs"] for r in tr.span_log()
               if r["name"] == "decode.prefill"
               and "linear_tokens" in r["attrs"]]
    assert sorted(a["linear_tokens"] for a in prefill[-6:]) == sorted(
        6 * len(p) for p, _new in reqs)


def test_a_reused_state_row_carries_nothing_of_its_first_session(model):
    """One slot, one state row: the second request decodes in the row the
    first left, and answers as it does alone."""
    params, cfg = model
    dcfg = DecodeConfig(slots=1, page_size=PAGE, num_pages=20,
                        max_context=64, queue_depth=4, max_new_tokens=12,
                        default_timeout_ms=120000)
    rng = np.random.RandomState(8)
    first = (rng.randint(0, 256, 30).tolist(), 12)
    second = (rng.randint(0, 256, 2).tolist(), 12)    # shorter than the conv
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        assert eng._state_rows.capacity == 1
        a, b = eng.submit(*first), eng.submit(*second)
        a.result()
        got = b.result()
        # the row holds what the second session left, not zeros
        assert np.asarray(eng._k_pages[1][:, 1]).any()
    finally:
        eng.close()
    assert got == _unbatched(params, cfg, dcfg, *second)
    fresh = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        assert fresh.submit(*second).result() == got
    finally:
        fresh.close()


def test_crash_recovery_zeroes_the_state_pools(model):
    from mxnet_tpu import fault
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=20,
                        max_context=64, queue_depth=4, max_new_tokens=8,
                        default_timeout_ms=120000, worker_restarts=1)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        prompt = list(range(9))
        want = eng.submit(prompt, 8).result()
        assert np.asarray(eng._k_pages[1]).any()
        with fault.arming("decode.step", step=3, kind="raise"):
            sess = eng.submit(prompt, 8)
            with pytest.raises(Exception):
                sess.result()
        assert eng._state_rows.used_pages == 0 \
            and eng._pool.used_pages == 0
        # the session fails inside the recovery, the pools follow it
        for _ in range(200):
            if not np.asarray(eng._k_pages[1]).any():
                break
            time.sleep(0.05)
        assert not np.asarray(eng._k_pages[1]).any()
        assert not np.asarray(eng._v_pages[1]).any()
        assert eng.submit(prompt, 8).result() == want
    finally:
        eng.close()


# -- what stays as it was, and what is refused ------------------------------

@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_training_block_refuses_each_new_field_by_name(field):
    base = dict(num_experts=4) if field.startswith("moe_") else {}
    cfg = TransformerConfig(**dict(base, **{field: NEW_FIELDS[field]}))
    assert getattr(TransformerConfig(), field) == T._TRAINABLE[field]
    with pytest.raises(ValueError, match=field):
        T._validate_trainable(cfg)


def test_training_step_refuses_the_model():
    with pytest.raises(ValueError, match="cannot run"):
        make_transformer_train_step(TransformerConfig(**MODEL), _mesh())


@pytest.mark.parametrize("change,match", [
    ({"linear_key_heads": 0}, "linear_key_heads"),
    ({"linear_value_heads": 3}, "linear_value_heads"),
    ({"linear_conv_width": 1}, "linear_conv_width"),
    ({"linear_layout": (1, 0)}, "linear_layout"),
    ({"sliding_window": 8}, "state cache"),
    ({"rotary_share": 0.3}, "rotary_share"),
    ({"norm": "layernorm"}, "norm_zero_centered"),
    ({"moe_shared_width": 0}, "moe_shared_gate"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_config_is_validated(change, match):
    with pytest.raises(ValueError, match=match):
        T._validate_config(TransformerConfig(**dict(MODEL, **change)))


def test_only_a_rotating_model_needs_an_even_rotated_width():
    odd = dict(vocab_size=64, d_model=30, n_heads=2, n_layers=1, d_ff=16)
    T._validate_config(TransformerConfig(**odd))        # learned positions
    with pytest.raises(ValueError, match="rotary_share"):
        T._validate_config(TransformerConfig(pos_type="rope", **odd))


def test_linear_model_has_pages_and_rows_and_no_dense_strip(model):
    params, cfg = model
    with pytest.raises(ValueError, match="state rows"):
        init_kv_cache(cfg, 1)
    with pytest.raises(ValueError, match="state rows"):
        init_kv_pages(cfg, 8, PAGE)
    kp, vp = init_kv_pages(cfg, (8, 3), PAGE)
    with pytest.raises(ValueError, match="LinearStateCache"):
        transformer_decode_step(
            params, paged_cache(kp[0], vp[0], jnp.zeros((1, 8), jnp.int32),
                                PAGE), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32), cfg)
    # and no other model runs over one
    plain = TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                              n_layers=2, d_ff=32)
    built, _ = init_transformer_params(plain, _mesh(), seed=2)
    cache = paged_cache(kp, vp, (jnp.zeros((1, 8), jnp.int32),
                                 jnp.zeros((1, 1), jnp.int32)), PAGE, cfg)
    with pytest.raises(ValueError, match="LinearStateCache"):
        transformer_decode_step(built, cache, jnp.zeros(1, jnp.int32),
                                jnp.zeros(1, jnp.int32), plain)
