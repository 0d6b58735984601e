"""DeepSeek-V3's block — latent attention (MLA) over a paged latent cache,
sigmoid group-limited routing with a bias in the choice and a shared expert,
a leading dense SwiGLU layer beside expert layers, and a device that holds
only SOME of the experts — through the framework's normal paths at a small
size, in float32, against the benchmark's plain reference
(``bench/reference/deepseek_v3.py``: no cache, no kernels, no absorption).
"""
import importlib.util
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from mxnet_tpu.ops.pallas.mla_attention import (  # noqa: E402
    _mla_flash_prefill_xla, _mla_paged_decode_xla, latent_pool_shape,
    mla_flash_prefill, mla_paged_decode, pages_of_latents)
from mxnet_tpu.ops.pallas.moe_ffn import (  # noqa: E402
    _moe_grouped_ffn_xla, f_tile, moe_grouped_ffn)
from mxnet_tpu.parallel import transformer as T  # noqa: E402
from mxnet_tpu.parallel.moe import (  # noqa: E402
    group_limited_routing, max_routed_tokens, moe_ffn_sorted,
    sorted_dispatch)
from mxnet_tpu.parallel.transformer import (  # noqa: E402
    LatentKVCache, TransformerConfig, init_kv_cache, init_kv_pages,
    init_transformer_params, make_transformer_train_step, paged_cache,
    transformer_decode_step, transformer_forward_single,
    transformer_prefill_paged)
from mxnet_tpu.serve import DecodeConfig, DecodeEngine  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CONTEXT = 4, 40
TOL = 2e-4
YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 16, "mscale": 1.0,
        "mscale_all_dim": 1.0}
MODEL = dict(vocab_size=256, d_model=64, n_heads=4, n_layers=3, d_ff=32,
             max_len=64, num_experts=16, moe_top_k=4, pos_type="rope",
             rope_base=10000.0, norm="rmsnorm", norm_eps=1e-6,
             tie_embeddings=False, moe_router="noaux_tc", kv_lora_rank=32,
             q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_scaling=YARN, dense_layers=1,
             d_ff_dense=96, gate_act="silu", moe_shared_width=32,
             moe_n_groups=4, moe_topk_groups=2, moe_routed_scale=2.5,
             moe_local_experts=(0, 4))
NEW_FIELDS = dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8,
                  qk_rope_head_dim=8, v_head_dim=8, rope_scaling=YARN,
                  dense_layers=1, d_ff_dense=8, gate_act="silu",
                  moe_shared_width=8, moe_n_groups=2, moe_topk_groups=2,
                  moe_routed_scale=2.5, moe_local_experts=(0, 2))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_deepseek_v3", os.path.join(
            ROOT, "bench", "reference", "deepseek_v3.py"))
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


def _latent_cache(cfg, rows, pages_per_seq):
    pages, none = init_kv_pages(cfg, rows * pages_per_seq + 1, PAGE)
    assert none is None
    table = 1 + np.arange(rows * pages_per_seq, dtype=np.int32)
    return paged_cache(pages, None, jnp.asarray(table.reshape(rows, -1)),
                       PAGE)


# -- the model against the reference ----------------------------------------

def test_reference_tree_is_the_programs(model):
    params, cfg = model
    built, _ = init_transformer_params(cfg, _mesh(), seed=1)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(built) == shapes(params)
    assert set(built) == {"embed", "head", "lnf_g", "dense_layers", "layers"}
    assert built["layers"]["we_gate"].shape == (1, 2, 4, 64, 32)  # 4 held
    assert built["layers"]["gate"].shape == (1, 2, 64, 16)    # 16 scored


def test_forward_single_matches_reference(model, sequence):
    params, cfg = model
    tokens, want, want_experts = sequence
    got, stats = transformer_forward_single(
        params, jnp.asarray(tokens[None]), cfg, with_stats=True)
    assert np.abs(np.asarray(got)[0] - want).max() <= TOL
    # the two EXPERT layers' choices, ids among all 16
    assert stats["moe_experts"].shape == (2, cfg.moe_top_k, CONTEXT)
    assert np.array_equal(
        np.sort(np.asarray(stats["moe_experts"]).transpose(0, 2, 1), -1),
        np.sort(want_experts, -1))
    assert want_experts.max() > 3            # most are not held here
    assert np.asarray(stats["moe_active_experts"]).max() <= 4


@pytest.mark.parametrize("change", [
    {"moe_n_groups": 1, "moe_topk_groups": 1},
    {"rope_scaling": dict(YARN, mscale=0.0, mscale_all_dim=0.0)},
    {"rope_scaling": None},
    {"moe_routed_scale": 1.0},
    {"gate_act": "relu"},
    {"moe_local_experts": (4, 4)},
], ids=["group_limit", "mscale_squared", "yarn_frequencies", "routed_scale",
        "silu_gate", "held_experts"])
def test_the_comparison_sees_a_wrong_rule(model, sequence, change):
    params, _cfg = model
    tokens, want, _experts = sequence
    wrong = TransformerConfig(**dict(MODEL, **change))
    got = transformer_forward_single(params, jnp.asarray(tokens[None]),
                                     wrong)
    assert np.abs(np.asarray(got)[0] - want).max() > 10 * TOL


def test_bias_moves_the_choice_and_not_the_weights(model, sequence):
    params, cfg = model
    tokens, want, want_experts = sequence
    flat = dict(params, layers=dict(
        params["layers"],
        gate_bias=jnp.zeros_like(params["layers"]["gate_bias"])))
    _got, stats = transformer_forward_single(
        flat, jnp.asarray(tokens[None]), cfg, with_stats=True)
    moved = np.sort(np.asarray(stats["moe_experts"]).transpose(0, 2, 1),
                    -1) != np.sort(want_experts, -1)
    assert 0.02 < moved.any(-1).mean() < 0.9


def _teacher_forced(params, cfg, cache, tokens, prompt, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt] = tokens[:prompt]
    logits, cache = transformer_prefill_paged(
        params, cache, jnp.asarray(padded),
        jnp.asarray([prompt], jnp.int32), cfg)
    rows = [np.asarray(logits)[0]]
    for pos in range(prompt, len(tokens)):
        logits, cache = transformer_decode_step(
            params, cache, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos], jnp.int32), cfg)
        rows.append(np.asarray(logits)[0])
    return np.stack(rows), cache


@pytest.mark.parametrize("prompt,bucket", [(13, 16), (5, 8), (30, 32)])
def test_prefill_then_decode_latent_cache_matches_reference(
        model, sequence, prompt, bucket):
    """The prompt attended decompressed, every later token absorbed over
    the paged latents the prefill and the steps wrote."""
    params, cfg = model
    tokens, want, _experts = sequence
    got, cache = _teacher_forced(params, cfg, _latent_cache(cfg, 1, 10),
                                 tokens, prompt, bucket)
    assert np.abs(got - want[prompt - 1:]).max() <= TOL
    assert isinstance(cache, LatentKVCache)
    assert cache.pages.shape == (3, 11, 1, 32 + 8, PAGE)  # transposed
    # the padded tail went to the null page: a row holds pages for its
    # tokens, not for its bucket
    used = -(-CONTEXT // PAGE)
    assert not np.asarray(cache.pages[:, used + 1:]).any()


def test_absorbed_attend_is_the_decompressed_attend(model):
    params, cfg = model
    lp = jax.tree_util.tree_map(lambda p: p[0, 1], params["layers"])
    rng = np.random.RandomState(2)
    s = 12
    h = jnp.asarray(rng.randn(1, s, 64), jnp.float32)
    c_q, latent = T._mla_compress(cfg, lp, h, jnp.arange(s)[None, :])
    whole = np.asarray(T._mla_attend_prompt(cfg, lp, c_q, latent))[0]
    cache = _latent_cache(cfg, 1, 3)
    cache = T._latent_write_prompt(cache, 1, latent, None)
    for t in (0, 5, s - 1):
        one = T._mla_attend_latent(cfg, lp, c_q[:, t], cache, 1,
                                   jnp.asarray([t], jnp.int32))
        assert np.abs(np.asarray(one)[0] - whole[t]).max() <= 1e-5


def test_long_prompts_attend_in_groups_of_heads(model, monkeypatch):
    params, cfg = model
    lp = jax.tree_util.tree_map(lambda p: p[0, 0], params["dense_layers"])
    h = jnp.asarray(np.random.RandomState(4).randn(2, 8, 64), jnp.float32)
    c_q, latent = T._mla_compress(cfg, lp, h, jnp.arange(8)[None, :])
    whole = T._mla_attend_prompt(cfg, lp, c_q, latent)
    monkeypatch.setattr(T, "_MLA_PREFILL_HEAD_ROWS", 8)   # one head a go
    by_group = T._mla_attend_prompt(cfg, lp, c_q, latent)
    assert np.abs(np.asarray(whole) - np.asarray(by_group)).max() <= 1e-5


def test_yarn_frequencies_and_scale():
    cfg = TransformerConfig(**dict(MODEL, qk_rope_head_dim=64, rope_scaling=dict(
        YARN, original_max_position_embeddings=4096)))
    got = T._rope_inv_freq(cfg, 64)
    # the published code's blend, written out
    dim, base, factor, orig = 64, 10000.0, 40, 4096
    corr = lambda turns: dim * math.log(orig / (turns * 2 * math.pi)) \
        / (2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), 63)
    want = []
    for i in range(32):
        extra = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    assert np.allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0 and np.isclose(got[-1] * factor,
                                        base ** (-62.0 / 64))
    m = 0.1 * math.log(40) + 1
    assert np.isclose(T._mla_scale(cfg), (16 + 64) ** -0.5 * m * m)
    assert np.isclose(m * m, 1.874, atol=1e-3)
    plain = TransformerConfig(**dict(MODEL, rope_scaling=None))
    assert np.allclose(T._rope_inv_freq(plain, 8),
                       10000.0 ** (-np.arange(4) / 4.0))


# -- routing ------------------------------------------------------------------

def _route_literally(logits, bias, k, n_groups, topk_groups, scale):
    """arXiv:2412.19437 sec. 2.1.2 and the published ``MoEGate``, a row at
    a time in plain Python; ties to the lower index."""
    experts, weights = [], []
    for row in np.asarray(logits, np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        choice = s + np.asarray(bias, np.float64)
        per = len(s) // n_groups
        group_score = [sum(sorted(choice[g * per:(g + 1) * per])[-2:])
                       for g in range(n_groups)]
        kept = sorted(range(n_groups),
                      key=lambda g: (-group_score[g], g))[:topk_groups]
        allowed = [e for e in range(len(s)) if e // per in kept]
        chosen = sorted(allowed, key=lambda e: (-choice[e], e))[:k]
        w = np.array([s[e] for e in chosen])
        experts.append(chosen)
        weights.append(w / (w.sum() + 1e-20) * scale)
    return np.array(experts), np.array(weights)


def test_group_limited_routing_matches_its_transcription():
    rng = np.random.RandomState(7)
    logits = rng.randn(64, 32).astype(np.float32)
    bias = (rng.randn(32) * 0.3).astype(np.float32)
    got_e, got_w = group_limited_routing(jnp.asarray(logits),
                                         jnp.asarray(bias), 6, 8, 4, 2.5)
    want_e, want_w = _route_literally(logits, bias, 6, 8, 4, 2.5)
    assert np.array_equal(np.asarray(got_e), want_e)
    assert np.allclose(np.asarray(got_w), want_w, atol=1e-6)
    assert np.allclose(np.asarray(got_w).sum(-1), 2.5, atol=1e-5)
    # the bias decides who is chosen and is absent from the weights
    s = 1 / (1 + np.exp(-logits))
    picked = np.take_along_axis(s, want_e, 1)
    assert np.allclose(np.asarray(got_w),
                       picked / picked.sum(-1, keepdims=True) * 2.5,
                       atol=1e-6)
    # no group leaves more than its share: 4 groups of 4 at most
    assert len(set(np.asarray(got_e)[0] // 4)) <= 4


def test_group_limited_routing_breaks_ties_by_index():
    # every expert scores the same: groups 0..1 stay, experts 0..3 chosen
    logits = jnp.zeros((3, 16), jnp.float32)
    e, w = group_limited_routing(logits, jnp.zeros((16,)), 4, 4, 2, 1.0)
    assert np.array_equal(np.asarray(e), [[0, 1, 2, 3]] * 3)
    assert np.allclose(np.asarray(w), 0.25)
    # two tied groups behind a better one; tied experts inside
    row = np.zeros(16, np.float32)
    row[8:12] = 1.0                          # group 2 leads
    want_e, _w = _route_literally(row[None], np.zeros(16), 6, 4, 2, 1.0)
    got_e, _w = group_limited_routing(jnp.asarray(row[None]),
                                      jnp.zeros((16,)), 6, 4, 2, 1.0)
    assert np.array_equal(np.asarray(got_e), want_e)
    assert list(want_e[0]) == [8, 9, 10, 11, 0, 1]


def test_no_groups_is_plain_top_k_of_the_biased_scores():
    rng = np.random.RandomState(1)
    logits, bias = rng.randn(9, 12), rng.randn(12) * 0.2
    e, _w = group_limited_routing(jnp.asarray(logits, jnp.float32),
                                  jnp.asarray(bias, jnp.float32), 3, 1, 1,
                                  1.0)
    want = np.argsort(-(1 / (1 + np.exp(-logits)) + bias), -1)[:, :3]
    assert np.array_equal(np.asarray(e), want)


# -- a device's share of the experts ----------------------------------------

def test_absent_assignments_take_no_row():
    experts = jnp.asarray([[0, 5, 9], [4, 5, 15], [6, 7, 1]], jnp.int32)
    src, dest, sizes, counts = sorted_dispatch(experts, 4, 2, first=4)
    assert list(np.asarray(counts)) == [1, 2, 1, 1]      # experts 4..7
    assert list(np.asarray(sizes)) == [2, 2, 2, 2]
    dest = np.asarray(dest)
    assert (dest >= 0).tolist() == [[False, True, False],
                                    [True, True, False],
                                    [True, True, False]]
    rows = np.asarray(src)
    assert rows.shape == ((3 * 3 + 4 * 1) // 2 * 2,)
    for t in range(3):
        for j in range(3):
            if dest[t, j] >= 0:
                assert rows[dest[t, j]] == t
    assert len(set(dest[dest >= 0])) == 5               # a row each


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_partial_outputs_sum_to_the_uncut_layer(model, shares):
    """Every device computes its own experts' part plus the shared expert;
    the parts, the shared expert counted once, are the uncut layer."""
    params, _cfg = model
    held = 16 // shares
    rng = np.random.RandomState(11)
    lp = jax.tree_util.tree_map(lambda p: p[0, 0], params["layers"])
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
    shared = np.asarray(T._gated_ffn(TransformerConfig(**MODEL), lp, "ws_",
                                     h))
    total, touched = shared.copy(), 0
    for i in range(shares):
        part, part_experts, active = layer(i * held, held)
        assert np.array_equal(part_experts, experts)   # all 16 are scored
        total += part - shared
        touched += active
    assert np.abs(total - whole).max() <= 1e-5
    assert touched == len(np.unique(experts))
    assert np.abs(whole - shared).max() > 1e-2          # the routed part


def test_held_experts_rows_are_chunked_by_bytes():
    assert max_routed_tokens(6, 2560, 2) == 4096    # the first MoE cell's
    assert max_routed_tokens(8, 7168, 2) == 1024
    rng = np.random.RandomState(5)
    n, d, f, e = 16, 8, 16, 4
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    logits = jnp.asarray(rng.randn(n, e), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(2, d, f), jnp.float32) for _ in "gu")
    wd = jnp.asarray(rng.randn(2, f, d), jnp.float32)
    import mxnet_tpu.parallel.moe as moe
    whole = moe_ffn_sorted(x, logits, wg, wu, wd, 2, first=1, act="silu")
    old = moe.MAX_ROUTED_ROW_BYTES
    moe.MAX_ROUTED_ROW_BYTES = 4 * 2 * d * 4        # 4 tokens a chunk
    try:
        assert max_routed_tokens(2, d, 4) == 4
        parts = moe_ffn_sorted(x, logits, wg, wu, wd, 2, first=1,
                               act="silu")
    finally:
        moe.MAX_ROUTED_ROW_BYTES = old
    assert np.abs(np.asarray(whole[0]) - np.asarray(parts[0])).max() < 1e-5
    assert np.array_equal(np.asarray(whole[1]), np.asarray(parts[1]))


# -- the kernels against their twins (Pallas interpreter) -------------------

@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("tf", [None, 128])
def test_grouped_ffn_kernel_tiles_f_like_its_twin(act, tf):
    """One kernel: whole experts (``tf`` None: f fits) or blocks of 128
    columns of f accumulated in float32, the layer an index into the
    stack."""
    rng = np.random.RandomState(9)
    e, h, f, tile = 3, 128, 384, 16
    experts = jnp.asarray(rng.randint(0, 5, (24, 2)), jnp.int32)
    x = jnp.asarray(rng.randn(24, h), jnp.float32)
    stack = lambda *shape: jnp.asarray(rng.randn(1, 2, e, *shape) * 0.1,
                                       jnp.float32)
    wg, wu, wd = stack(h, f), stack(h, f), stack(f, h)
    src, dest, sizes, counts = sorted_dispatch(experts, e, tile, first=1)
    rows = x[src]
    got = moe_grouped_ffn(rows, sizes, wg, wu, wd, tile, interpret=True,
                          lead=(0, 1), act=act, tf=tf)
    want = _moe_grouped_ffn_xla(rows, sizes, wg, wu, wd, (0, 1), act)
    live = int(np.asarray(sizes).sum())
    assert np.abs(np.asarray(got)[:live] - np.asarray(want)[:live]).max() \
        <= 1e-4
    assert int(np.asarray(counts).sum()) < experts.size   # some were absent


def test_f_is_tiled_where_an_expert_does_not_fit():
    assert f_tile(2560, 768, 2) == 768            # the first MoE cell: whole
    assert f_tile(7168, 2048, 2) == 512           # 88 MB an expert: tiled
    assert f_tile(64, 32, 4) == 32
    with pytest.raises(ValueError, match="act"):
        moe_grouped_ffn(jnp.zeros((16, 8)), jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1, 8, 8)), jnp.zeros((1, 8, 8)),
                        jnp.zeros((1, 8, 8)), 16, act="gelu")


@pytest.mark.parametrize("ps", [8, 256])       # one lane tile a page; two
@pytest.mark.parametrize("layer", [None, 2])
def test_mla_decode_kernel_matches_its_twin(layer, ps):
    """The absorbed attend over a paged pool: rows at different depths,
    pages in any order, the layer a scalar-prefetched operand."""
    rng = np.random.RandomState(3)
    b, heads, rank, rope, entries = 3, 8, 128, 64, 4
    shape = latent_pool_shape(3, 13, ps, rank + rope)
    pages = jnp.asarray(rng.randn(*(shape if layer is not None
                                    else shape[1:])), jnp.float32)
    q = jnp.asarray(rng.randn(b, heads, rank + rope), jnp.float32)
    tables = jnp.asarray(rng.permutation(12)[:b * entries].reshape(
        b, entries) + 1, jnp.int32)
    lengths = jnp.asarray([1, 2 * ps + 3, 4 * ps], jnp.int32)
    got = mla_paged_decode(q, pages, tables, lengths, 0.11, rank,
                           interpret=True, layer=layer)
    want = _mla_paged_decode_xla(q, pages, tables, lengths, 0.11, rank,
                                 layer)
    assert got.shape == (b, heads, rank)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5
    if layer is not None:       # another layer's pages give another answer
        other = mla_paged_decode(q, pages, tables, lengths, 0.11, rank,
                                 interpret=True, layer=0)
        assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-2
    with pytest.raises(ValueError, match="layer"):
        mla_paged_decode(q, pages, tables, lengths, 0.11, rank,
                         layer=None if layer is not None else 0)


def test_latent_pages_are_transposed_lane_tiles():
    assert latent_pool_shape(5, 897, 512, 576) == (5, 897, 4, 576, 128)
    assert latent_pool_shape(3, 7, 8, 40) == (3, 7, 1, 40, 8)
    with pytest.raises(ValueError, match="lane tiles"):
        latent_pool_shape(1, 2, 192, 8)
    latent = jnp.arange(2 * 512 * 3, dtype=jnp.float32).reshape(2, 512, 3)
    pages = np.asarray(pages_of_latents(latent, 256))
    assert pages.shape == (2, 2, 2, 3, 128)
    # position 300 of row 1: page 1, tile 0, lane 44
    assert np.array_equal(pages[1, 1, 0, :, 44], np.asarray(latent)[1, 300])


@pytest.mark.parametrize("s,block", [(32, 8), (16, 16)])
def test_mla_prefill_kernel_matches_its_twin(s, block):
    rng = np.random.RandomState(6)
    b, heads, dn, dr, dv = 2, 3, 16, 8, 16
    arr = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q_nope, q_rope = arr(b, heads, s, dn), arr(b, heads, s, dr)
    k_nope, k_rope, v = arr(b, heads, s, dn), arr(b, s, dr), arr(b, heads,
                                                                  s, dv)
    got = mla_flash_prefill(q_nope, q_rope, k_nope, k_rope, v, 0.3,
                            block_q=block, block_k=block, interpret=True)
    want = _mla_flash_prefill_xla(q_nope, q_rope, k_nope, k_rope, v, 0.3)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5
    with pytest.raises(ValueError, match="multiple"):
        mla_flash_prefill(q_nope, q_rope, k_nope, k_rope, v, 0.3,
                          block_q=12, block_k=block, interpret=True)


# -- through DecodeEngine ---------------------------------------------------

def test_engine_serves_the_latent_pool_as_the_reference(model):
    from mxnet_tpu import telemetry as tm, tracing as tr
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        slots=4, page_size=PAGE, num_pages=60, max_context=64,
        queue_depth=16, max_new_tokens=24,
        default_timeout_ms=120000)).start().warmup()
    absent0 = tm.counter("decode/moe_absent_assignments_total").value
    rows0 = tm.counter("decode/moe_assignments_total").value
    try:
        assert eng._v_pages is None \
            and eng._k_pages.shape == (3, 60, 1, 40, PAGE)
        free = tm.gauge("decode/pages_free")
        assert free.labels("latent").value == 59
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 256, n).tolist(), new) for n, new in
                [(3, 20), (20, 12), (9, 24), (33, 5), (14, 9)]]
        # a prompt of 9 prefills in a bucket of 16 and answers 24: pages
        # for its 33 positions, not for the bucket
        sessions = [eng.submit(p, new) for p, new in reqs]
        assert len(sessions[2].page_ids) == -(-33 // PAGE)
        assert len(sessions[3].page_ids) == -(-38 // PAGE)    # bucket 64
        outs = [s.result() for s in sessions]
        assert eng._pool.used_pages == 0
        assert free.labels("latent").value == 59 \
            == free.labels("global").value
    finally:
        eng.close()
    total = held = 0
    for (prompt, new), out, sess in zip(reqs, outs, sessions):
        assert len(out) == new
        logits, experts = REF.forward(params, np.asarray(prompt + out),
                                      MODEL)
        rows = np.asarray(logits)[len(prompt) - 1:len(prompt) - 1 + new]
        gap = rows.max(-1) - rows[np.arange(new), np.asarray(out)]
        assert gap.max() <= TOL
        served = np.concatenate(sess.expert_choices, axis=1)
        assert served.shape[0] == 2               # the expert layers
        assert np.array_equal(np.sort(served, -1),
                              np.sort(np.asarray(experts)[:, :-1], -1))
        total += served.size
        held += int((served < 4).sum())
    assert tm.counter("decode/moe_assignments_total").value - rows0 == total
    assert tm.counter("decode/moe_absent_assignments_total").value \
        - absent0 == total - held
    steps = [r["attrs"] for r in tr.span_log() if r["name"] == "decode.step"
             and "latent_context_tokens" in r["attrs"]]
    assert steps
    last = steps[-1]
    assert last["latent_context_tokens"] == last["context_tokens"] * 3
    assert last["moe_assignments"] % (cfg.moe_top_k * 2) == 0
    assert 0 <= last["moe_rows"] <= last["moe_assignments"]
    assert last["moe_active_experts"] <= min(last["moe_rows"], 2 * 4)
    prefill = [r["attrs"] for r in tr.span_log()
               if r["name"] == "decode.prefill"
               and "latent_context_tokens" in r["attrs"]][-1]
    assert prefill["latent_context_tokens"] == 14 * 3
    assert prefill["moe_assignments"] == 14 * cfg.moe_top_k * 2


# -- what stays as it was, and what is refused ------------------------------

@pytest.mark.parametrize("sizes", [
    dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
         max_len=32),
    dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
         n_layers=4, d_ff=24, max_len=32, num_experts=4, moe_top_k=2,
         pos_type="rope", norm="rmsnorm", tie_embeddings=False,
         moe_router="topk", moe_router_input="layer", sliding_window=8,
         window_layout=[0, 1] * 2, rope_layout=[0, 1] * 2),
], ids=["gpt2_block", "smallthinker_block"])
def test_earlier_models_build_and_run_unchanged(sizes):
    cfg = TransformerConfig(**sizes)
    params, _ = init_transformer_params(cfg, _mesh(), seed=2)
    assert "dense_layers" not in params and "wq" in params["layers"]
    tokens = np.random.RandomState(1).randint(0, 64, 12)
    whole = np.asarray(transformer_forward_single(
        params, jnp.asarray(tokens[None]), cfg))[0]
    cache = init_kv_cache(cfg, 1, max_len=32)
    rows = []
    for pos in range(12):
        logits, cache = transformer_decode_step(
            params, cache, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos], jnp.int32), cfg)
        rows.append(np.asarray(logits)[0])
    assert np.abs(np.stack(rows) - whole).max() <= 1e-4
    k_pages, v_pages = init_kv_pages(cfg, 4, 4) if not cfg.sliding_window \
        else init_kv_pages(cfg, (4, 4), 4)
    assert v_pages is not None


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
    ({"q_lora_rank": 0}, "q_lora_rank"),
    ({"sliding_window": 8}, "no window"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "yarn"),
    ({"rope_scaling": dict(YARN, mscale=0.5)}, "mscale"),
    ({"dense_layers": 4}, "dense_layers"),
    ({"d_ff_dense": 0}, "d_ff_dense"),
    ({"gate_act": "gelu"}, "gate_act"),
    ({"moe_n_groups": 3}, "groups"),
    ({"moe_topk_groups": 5}, "groups"),
    ({"moe_local_experts": (12, 8)}, "moe_local_experts"),
    ({"moe_router": "capacity"}, "drop-free"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_config_is_validated(change, match):
    with pytest.raises(ValueError, match=match):
        T._validate_config(TransformerConfig(**dict(MODEL, **change)))


def test_latent_model_has_one_kind_of_cache(model):
    params, cfg = model
    with pytest.raises(ValueError, match="paged latent cache"):
        init_kv_cache(cfg, 1)
    plain = TransformerConfig(vocab_size=256, d_model=64)
    with pytest.raises(ValueError, match="LatentKVCache"):
        transformer_decode_step(params, init_kv_cache(plain, 1),
                                jnp.zeros((1,), jnp.int32), 0, cfg)
    cache = _latent_cache(cfg, 2, 4)
    assert cache.max_context == 16
    leaves, tree = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 2 and jax.tree_util.tree_unflatten(
        tree, leaves).page_size == PAGE
