"""Phi-4-mini-flash-reasoning's block (SambaY) — Mamba layers whose state
lives in a state row, window and full differential attention over paged
K/V pairs, and a cross-decoder whose gated memory units read one Mamba
layer's scan output and whose cross-attention layers read one full layer's
pages — through the framework's normal paths at a small size, in float32,
against the benchmark's plain reference
(``bench/reference/phi4_mini_flash_reasoning.py``: no cache, no kernels,
every layer over every position, the scan token by token).
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from mxnet_tpu.ops.pallas import diff_attention as DA  # noqa: E402
from mxnet_tpu.ops.pallas import mamba as MB  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402
from mxnet_tpu.parallel.transformer import (  # noqa: E402
    LinearStateCache, TransformerConfig, init_kv_cache, init_kv_pages,
    init_transformer_params, kv_layer_kinds, make_transformer_train_step,
    paged_cache, transformer_decode_step, transformer_forward_single,
    transformer_prefill_paged)
from mxnet_tpu.serve import DecodeConfig, DecodeEngine  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, WINDOW = 8, 16
TOL = 2e-5
N_LAYERS = 12
# [mamba, window] three times and [mamba, full] before the cross-decoder,
# then [gmu, cross] twice: the published layout at 12 layers (the memory is
# layer n/2's, the K/V layer n/2 + 1's, cross_from n/2 + 2)
MODEL = dict(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=4,
             head_dim=16, n_layers=N_LAYERS, d_ff=96, max_len=128,
             pos_type="none", norm="layernorm", norm_eps=1e-5,
             tie_embeddings=True, gate_act="silu", ffn_gated=True,
             sliding_window=WINDOW,
             window_layout=tuple(int(li < N_LAYERS // 2)
                                 for li in range(N_LAYERS)),
             mamba_layout=tuple(int(li % 2 == 0) for li in range(N_LAYERS)),
             mamba_d_state=4, mamba_d_conv=4, mamba_expand=2,
             mamba_dt_rank=4, cross_from=N_LAYERS // 2 + 2, diff_attn=True,
             attn_bias=True)
NEW_FIELDS = dict(mamba_layout=(1, 0), mamba_d_state=4, mamba_d_conv=4,
                  mamba_expand=2, mamba_dt_rank=4, cross_from=1,
                  diff_attn=True, attn_bias=True, ffn_gated=True)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_phi4_flash", os.path.join(
            ROOT, "bench", "reference", "phi4_mini_flash_reasoning.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))


def _draw(model, seed=3):
    """The reference's tree drawn as the benchmark draws it, float32, its
    fixed leaves set."""
    rng = np.random.RandomState(seed)

    def leaf(spec):
        shape, kind = spec
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        return jnp.asarray(rng.randn(*shape) * REF.init_std(kind, model),
                           jnp.float32)

    return REF.with_fixed(jax.tree_util.tree_map(
        leaf, REF.param_tree(model),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str)),
        model)


@pytest.fixture(scope="module")
def model():
    return _draw(MODEL), TransformerConfig(**MODEL)


@pytest.fixture(scope="module")
def sequence(model):
    params, _cfg = model
    tokens = np.random.RandomState(0).randint(0, 256, 60)
    return tokens, np.asarray(REF.forward(params, tokens, MODEL))


def _pools(cfg, rows, state_rows=None):
    """Pools for ``rows`` sequences: the full layer's pages, window rings
    of ``WINDOW / PAGE + 1`` entries, state rows 1 ..."""
    per = CONTEXT_PAGES
    ring = WINDOW // PAGE + 1
    kp, vp = init_kv_pages(cfg, ((rows * per + 1, rows * ring + 1),
                                 (state_rows or rows) + 1), PAGE)
    full = 1 + np.arange(rows * per, dtype=np.int32).reshape(rows, per)
    rings = 1 + np.arange(rows * ring, dtype=np.int32).reshape(rows, ring)
    return kp, vp, jnp.asarray(full), jnp.asarray(rings)


CONTEXT_PAGES = 12


def _layer(params, stack, i=0):
    return jax.tree_util.tree_map(lambda p: p[0, i], params[stack])


# -- the model against the reference ----------------------------------------

def test_reference_tree_is_the_programs(model):
    """The tree the benchmark draws is the one the program builds."""
    _params, cfg = model
    built, _specs = init_transformer_params(cfg, _mesh(), seed=1)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: tuple(x.shape), t)
    tree = jax.tree_util.tree_map(
        lambda s: s[0], REF.param_tree(MODEL),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    assert shapes(built) == tree
    assert kv_layer_kinds(cfg) == tuple(REF.kinds(MODEL)) == (
        "mamba", "window") * 3 + ("mamba", "full") + ("gmu", "cross") * 2


def test_forward_single_matches_reference(model, sequence):
    params, cfg = model
    tokens, want = sequence
    got = transformer_forward_single(params, jnp.asarray(tokens[None]), cfg)
    assert np.abs(np.asarray(got[0]) - want).max() <= TOL


def test_half_model_prefill_gives_the_full_forwards_last_logits(model,
                                                                sequence):
    """The prefill runs the cross-decoder on each row's last REAL position
    alone; its logits are the whole forward's at that position, for rows
    of several lengths in one bucket."""
    params, cfg = model
    tokens, want = sequence
    lengths = np.array([40, 23, 3], np.int32)
    kp, vp, full, rings = _pools(cfg, 3)
    cache = paged_cache(kp, vp, ((full, rings), jnp.arange(1, 4)[:, None]),
                        PAGE, cfg)
    padded = np.zeros((3, 40), np.int32)
    for r, n in enumerate(lengths):
        padded[r, :n] = tokens[:n]
    logits, _cache = transformer_prefill_paged(
        params, cache, jnp.asarray(padded), jnp.asarray(lengths), cfg)
    for r, n in enumerate(lengths):
        assert np.abs(np.asarray(logits[r]) - want[n - 1]).max() <= TOL


@pytest.mark.parametrize("prompt", [2, 30])
def test_prefill_then_decode_matches_reference(model, sequence, prompt):
    """Prefill, then decode through the three kinds of state: the Mamba
    layers' state rows and convolution tails handed from the prefill (a
    prompt of 2 is shorter than the convolution), the window layers'
    rings wrapping past the window (60 positions on rings of 3 pages of
    8), the full layer's pages read by every cross layer."""
    params, cfg = model
    tokens, want = sequence
    kp, vp, full, rings = _pools(cfg, 1, state_rows=2)
    rows = jnp.asarray([[2]], jnp.int32)            # not the first row
    cache = paged_cache(kp, vp, ((full, rings), rows), PAGE, cfg)
    bucket = -(-prompt // PAGE) * PAGE
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt] = tokens[:prompt]
    logits, cache = transformer_prefill_paged(
        params, cache, jnp.asarray(padded), jnp.asarray([prompt]), cfg)
    gaps = [np.abs(np.asarray(logits[0]) - want[prompt - 1]).max()]
    step = jax.jit(functools.partial(transformer_decode_step, cfg=cfg))
    for pos in range(prompt, len(tokens)):
        logits, cache = step(params, cache, jnp.asarray([tokens[pos]]),
                             jnp.asarray([pos]))
        gaps.append(np.abs(np.asarray(logits[0]) - want[pos]).max())
    assert max(gaps) <= TOL
    assert np.abs(np.asarray(cache.state[:, :2])).max() == 0.0  # rows 0, 1


def test_one_full_layer_is_read_by_every_cross_layer(model):
    """The full pool holds ONE layer; every cross layer is given its pages
    and writes nothing; the window pool holds the window layers."""
    params, cfg = model
    kp, vp, full, rings = _pools(cfg, 1)
    (k_full, k_window), state = kp
    assert k_full.shape[0] == 1 and k_window.shape[0] == 3
    # flat pages: a page's (token, pair) rows, pairs of 2 heads of 16
    assert k_full.shape[2:] == (PAGE * 2, 32)
    assert state.shape == (4, 2, 4, 128) and state.dtype == jnp.float32
    assert vp[1].shape == (4, 2, 3 * 128)
    cache = paged_cache(kp, vp, ((full, rings), jnp.asarray([[1]])), PAGE,
                        cfg)
    assert T._yoco_sources(cfg) == (7, 6)
    readers = [li for li, k in enumerate(kv_layer_kinds(cfg))
               if k == "cross"] + [7]
    for li in readers:
        part, idx, put = T._layer_cache(cache, cfg, li)
        assert part is cache.full.full and idx == 0
        if li != 7:
            assert put(None) is cache               # nothing written


def test_engine_serves_three_kinds_of_state_as_the_reference(model):
    """Batched continuous decode == the reference's greedy choice, token
    for token; rows, rings and pages come back; the spans count the new
    work."""
    from mxnet_tpu import tracing as tr
    params, cfg = model
    dcfg = DecodeConfig(slots=4, page_size=PAGE, num_pages=60,
                        window_pages=40, max_context=80, queue_depth=16,
                        max_new_tokens=40, default_timeout_ms=120000)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        (k_full, k_window), state = eng._k_pages
        assert k_full.shape[0] == 1 and k_window.shape[0] == 3
        assert state.shape == (4, 5, 4, 128)        # a row a slot + null
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 256, n).tolist(), new) for n, new in
                [(3, 6), (12, 30), (25, 20), (2, 8), (33, 12), (1, 7)]]
        sessions = [eng.submit(p, new) for p, new in reqs]
        outs = [s.result() for s in sessions]
        assert eng._pool.used_pages == eng._wpool.used_pages \
            == eng._state_rows.used_pages == 0
    finally:
        eng.close()
    for (prompt, new), out in zip(reqs, outs):
        logits = np.asarray(REF.forward(params, np.asarray(prompt + out),
                                        MODEL))
        rows = logits[len(prompt) - 1:len(prompt) - 1 + new]
        gap = rows.max(-1) - rows[np.arange(new), np.asarray(out)]
        assert gap.max() <= TOL
    log = tr.span_log()
    steps = [r["attrs"] for r in log if r["name"] == "decode.step"
             and "mamba_rows" in r["attrs"]]
    assert steps and all(a["mamba_rows"] == 4 * a["state_rows"]
                         and a["shared_kv_readers"] == 3 for a in steps)
    assert all(a["full_pages_read"] >= a["state_rows"]
               and a["window_pages_read"] <= 3 * a["state_rows"]
               for a in steps)
    prefill = [r["attrs"] for r in log if r["name"] == "decode.prefill"
               and "mamba_tokens" in r["attrs"]][-6:]
    assert sorted(a["mamba_tokens"] for a in prefill) == sorted(
        4 * len(p) for p, _new in reqs)
    assert all(a["cross_decoder_tokens"] == 1
               and a["self_decoder_only_tokens"] * 4 + 4
               == a["mamba_tokens"] for a in prefill)


# -- the mechanisms, one at a time ------------------------------------------

def test_lambda_is_two_exponentials_and_its_init(model):
    params, _cfg = model
    lp = _layer(params, "layers", 1)
    for li in (3, 17):
        lam, init = T._diff_lambda(lp, li)
        dot = lambda a, b: float(np.sum(np.asarray(lp[a] * lp[b])))
        want = (np.exp(dot("lambda_q1", "lambda_k1"))
                - np.exp(dot("lambda_q2", "lambda_k2")) + init)
        assert init == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * li))
        assert float(lam) == pytest.approx(want, rel=1e-6)
        assert abs(float(lam) - init) > 1e-3        # the vectors count


def test_sub_norm_and_lambda_combine_the_two_maps(model):
    """``_diff_out``: a head's A1 v - lambda A2 v, an RMSNorm over its 2 hd
    with the gain, times 1 - lambda_init, through the biased output map —
    so a head's reads scaled together change nothing."""
    params, cfg = model
    lp = dict(_layer(params, "layers", 1),
              subln_g=jnp.asarray(np.random.RandomState(1).randn(32),
                                  jnp.float32))
    o = jnp.asarray(np.random.RandomState(2).randn(3, 2, 4, 32), jnp.float32)
    got = np.asarray(T._diff_out(cfg, lp, 3, o))
    lam, init = T._diff_lambda(lp, 3)
    a = np.asarray(o).reshape(3, 2, 2, 2, 32)       # (b, pair, map, e, w)
    d = (a[:, :, 0] - float(lam) * a[:, :, 1]).reshape(3, 4, 32)
    d = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(lp["subln_g"]) * (1 - init)
    want = d.reshape(3, 128) @ np.asarray(lp["wo"]) + np.asarray(lp["bo"])
    assert np.abs(got - want).max() <= 1e-4
    assert np.abs(np.asarray(T._diff_out(cfg, lp, 3, 7.0 * o))
                  - got).max() <= 1e-4


def test_query_rows_against_pairs_give_both_softmax_maps(model):
    """``_diff_queries``' rows against the K/V pairs are exactly the two
    maps of every differential head over the pair's whole value."""
    _params, cfg = model
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 9, 8, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 9, 4, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 9, 4, 16), jnp.float32)
    got = np.asarray(T._diff_attend_seq(cfg, q, k, v))   # (1, 9, 2, 4, 32)
    mask = np.tril(np.ones((9, 9), bool))
    for i in range(4):                     # differential head i, pair i // 2
        j = i // 2
        vv = np.concatenate([v[0, :, 2 * j], v[0, :, 2 * j + 1]], -1)
        for m in range(2):
            sc = np.asarray(q[0, :, 2 * i + m] @ k[0, :, 2 * j + m].T) / 4
            p = np.exp(np.where(mask, sc, -np.inf) - sc.max())
            p /= p.sum(-1, keepdims=True)
            assert np.abs(got[0, :, j, m * 2 + i % 2] - p @ vv).max() < 1e-5


def test_window_layers_see_only_their_window(model):
    """A key ``window`` or more positions behind a query moves nothing in a
    window layer, and moves the full layer's read."""
    _params, cfg = model
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 40, 8, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 40, 4, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 40, 4, 16), jnp.float32)
    moved = k.at[0, 39 - WINDOW].add(3.0)
    last = lambda kk, w: np.asarray(T._diff_attend_seq(cfg, q, kk, v, w))[
        0, 39]
    assert np.abs(last(moved, WINDOW) - last(k, WINDOW)).max() == 0.0
    assert np.abs(last(moved, None) - last(k, None)).max() > 1e-3
    inside = k.at[0, 40 - WINDOW].add(3.0)
    assert np.abs(last(inside, WINDOW) - last(k, WINDOW)).max() > 1e-3


def test_the_memory_is_the_last_mamba_layers_gated_read_out(model):
    """The GMUs read ``y * SiLU(z)`` of the Mamba layer before the full one
    (``_yoco_sources``): a forward whose memory came from an earlier Mamba
    layer is another model, and the reference tells them apart."""
    params, cfg = model
    assert T._yoco_sources(TransformerConfig(**dict(
        MODEL, n_layers=32, window_layout=(1,) * 16 + (0,) * 16,
        mamba_layout=(1, 0) * 16, cross_from=18))) == (17, 16)
    lp = _layer(params, "mamba_layers", 2)
    rng = np.random.RandomState(7)
    y = jnp.asarray(rng.randn(5, 128), jnp.float32)
    z = jnp.asarray(rng.randn(5, 128), jnp.float32)
    out, m = T._mamba_out(lp, y, z)
    assert np.abs(np.asarray(m) - np.asarray(y * jax.nn.silu(z))).max() \
        < 1e-6
    h = jnp.asarray(rng.randn(5, 64), jnp.float32)
    g = _layer(params, "gmu_layers", 0)
    want = (np.asarray(m) * np.asarray(jax.nn.silu(h @ g["gmu_in"]))) \
        @ np.asarray(g["gmu_out"])
    assert np.abs(np.asarray(T._gmu(g, h, m)) - want).max() < 1e-5
    # memory from layer 2 instead of layer 6: the comparison sees it
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (1, 24)))
    real = np.asarray(transformer_forward_single(params, tokens, cfg))
    orig = T._mamba_prompt
    seen = []

    def early(cfg_, lp_, h_, lengths=None):
        # every Mamba layer after the second hands on the second's memory
        out, m, state, tail = orig(cfg_, lp_, h_, lengths)
        seen.append(m)
        return out, seen[min(1, len(seen) - 1)], state, tail

    T._mamba_prompt = early
    try:
        wrong = np.asarray(transformer_forward_single(params, tokens, cfg))
    finally:
        T._mamba_prompt = orig
    assert np.abs(wrong - real).max() > 10 * TOL


def test_delta_is_the_softplus_of_the_step_map_and_closed_on_padding(model):
    params, cfg = model
    lp = _layer(params, "mamba_layers", 1)
    h = jnp.asarray(np.random.RandomState(8).randn(2, 6, 64), jnp.float32)
    x, _z, delta, bm, cm, _tail = T._mamba_inputs(
        cfg, lp, h, lengths=jnp.asarray([6, 4]))
    dbc = np.asarray(x) @ np.asarray(lp["mb_x"])
    raw = dbc[..., :4] @ np.asarray(lp["mb_dt"]) + np.asarray(lp["mb_dt_b"])
    want = np.log1p(np.exp(raw))
    assert np.abs(np.asarray(delta[0]) - want[0]).max() < 1e-5
    assert np.abs(np.asarray(delta[1, :4]) - want[1, :4]).max() < 1e-5
    assert (np.asarray(delta[1, 4:]) == 0).all()        # padding: no step
    assert np.abs(np.asarray(bm) - dbc[..., 4:8]).max() < 1e-5
    assert np.abs(np.asarray(cm) - dbc[..., 8:]).max() < 1e-5
    # the steps start where Mamba sets them: 1e-3 .. 1e-1
    b = np.asarray(lp["mb_dt_b"])
    assert np.log1p(np.exp(b)).min() == pytest.approx(1e-3, rel=1e-3)
    assert np.log1p(np.exp(b)).max() == pytest.approx(1e-1, rel=1e-3)


def test_conv_tail_and_state_are_handed_from_prefill_to_decode(model):
    """A Mamba layer's prefill leaves in its row exactly the state and the
    last d_conv - 1 inputs that token-by-token steps would — for a prompt
    of 8 and one of 2, shorter than the convolution."""
    params, cfg = model
    lp = _layer(params, "mamba_layers", 0)
    h = jnp.asarray(np.random.RandomState(9).randn(2, 8, 64), jnp.float32)
    lengths = [8, 2]
    _out, _m, state, tail = T._mamba_prompt(cfg, lp, h, jnp.asarray(lengths))
    for r, n in enumerate(lengths):
        cache = LinearStateCache(None, jnp.zeros((3, 2, 4, 128), jnp.float32),
                                 jnp.zeros((3, 2, 3 * 128), jnp.float32),
                                 jnp.asarray([1]))
        for t in range(n):
            _o, _m, cache = T._mamba_token(cfg, lp, h[r:r + 1, t], cache, 1)
        assert np.abs(np.asarray(state[r])
                      - np.asarray(cache.state[1, 1])).max() < 1e-5
        assert np.abs(np.asarray(tail[r]).reshape(-1)
                      - np.asarray(cache.conv[1, 1])).max() < 1e-6


# -- the kernels against their twins and the plain rule ---------------------

def _scan_inputs(seed, b, s, ch, ns, lengths=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(b, s, ch)
    delta = np.abs(rng.randn(b, s, ch)) * 0.1
    if lengths is not None:
        delta = np.where((np.arange(s)[None, :] < np.asarray(lengths)[
            :, None])[..., None], delta, 0.0)
    bm, cm = rng.randn(b, s, ns), rng.randn(b, s, ns)
    a = -np.exp(rng.randn(ns, ch) * 0.5)
    d = rng.randn(ch)
    return tuple(jnp.asarray(t, jnp.float32)
                 for t in (x, delta, bm, cm, a, d))


@pytest.mark.parametrize("lengths", [None, [20, 7]])
def test_chunked_scan_is_the_token_by_token_rule(lengths):
    x, delta, bm, cm, a, d = _scan_inputs(0, 2, 20, 128, 4, lengths)
    y, state = MB._mamba_scan_xla(x, delta, bm, cm, a, d)
    pool = jnp.zeros((1, 3, 4, 128), jnp.float32)
    rows = jnp.asarray([1, 2])
    for t in range(20):
        y_t, pool = MB._mamba_step_xla(x[:, t], delta[:, t], bm[:, t],
                                       cm[:, t], a, d, pool, rows, 0)
        assert np.abs(np.asarray(y_t) - np.asarray(y[:, t])).max() < 1e-5
    assert np.abs(np.asarray(pool[0, 1:]) - np.asarray(state)).max() < 1e-5


def test_scan_kernel_matches_its_twin():
    """Two chunks and a padded tail (delta 0) in the Pallas interpreter."""
    args = _scan_inputs(1, 2, 200, 256, 16, [200, 150])
    y1, s1 = MB._mamba_scan_xla(*args)
    y2, s2 = MB.mamba_chunk_scan(*args, interpret=True)
    assert np.abs(np.asarray(y1) - np.asarray(y2)).max() \
        <= 1e-5 * np.abs(np.asarray(y1)).max()
    assert np.abs(np.asarray(s1) - np.asarray(s2)).max() < 1e-5


def test_step_kernel_matches_its_twin_in_place():
    x, delta, bm, cm, a, d = (t[:, 0] if t.ndim == 3 else t
                              for t in _scan_inputs(2, 3, 1, 256, 16))
    pool = jnp.asarray(np.random.RandomState(3).randn(3, 5, 16, 256),
                       jnp.float32)
    rows = jnp.asarray([4, 0, 2], jnp.int32)
    y1, p1 = MB._mamba_step_xla(x, delta, bm, cm, a, d, pool, rows, 1)
    y2, p2 = MB.mamba_recurrent_step(x, delta, bm, cm, a, d, pool, rows, 1,
                                     interpret=True)
    assert np.abs(np.asarray(y1) - np.asarray(y2)).max() < 1e-5
    assert np.abs(np.asarray(p1) - np.asarray(p2)).max() < 1e-5
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [4, 0, 2]] = False
    assert (np.asarray(p2)[untouched] == np.asarray(pool)[untouched]).all()


@pytest.mark.parametrize("window", [None, 16])
def test_diff_paged_kernel_matches_its_twin(window):
    """Flat pages of 4 pairs, a ring of 3 entries under a window."""
    rng = np.random.RandomState(5)
    pairs, g, w, ps = 4, 4, 128, 8
    kp = jnp.asarray(rng.randn(2, 30, ps * pairs, w), jnp.float32)
    vp = jnp.asarray(rng.randn(2, 30, ps * pairs, w), jnp.float32)
    q = jnp.asarray(rng.randn(3, pairs, g, w), jnp.float32)
    entries = 3 if window else 6
    bt = jnp.asarray(rng.randint(1, 30, (3, entries)), jnp.int32)
    lengths = jnp.asarray([5, 40 if window else 46, 17], jnp.int32)
    o1 = DA._diff_paged_decode_xla(q, kp, vp, bt, lengths, 0.25, window, 1)
    o2 = DA.diff_paged_decode(q, kp, vp, bt, lengths, ps, 0.25,
                              window=window, layer=1, interpret=True)
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 1e-5


@pytest.mark.parametrize("window", [None, 16])
def test_diff_prefill_kernel_matches_its_twin(window):
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(2, 64, 16, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 4, 128), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 4, 128), jnp.float32)
    o1 = DA._diff_prefill_xla(q, k, v, 0.125, window)
    o2 = DA.diff_flash_prefill(q, k, v, 0.125, window, block_q=32,
                               block_k=32, interpret=True)
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 1e-5


# -- what stays as it was, and what is refused ------------------------------

@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_training_block_refuses_each_new_field_by_name(field):
    cfg = TransformerConfig(**{field: NEW_FIELDS[field]})
    assert getattr(TransformerConfig(), field) == T._TRAINABLE[field]
    with pytest.raises(ValueError, match=field):
        T._validate_trainable(cfg)


def test_training_step_refuses_the_model():
    with pytest.raises(ValueError, match="cannot run"):
        make_transformer_train_step(TransformerConfig(**MODEL), _mesh())


@pytest.mark.parametrize("change,match", [
    ({"mamba_d_state": 0}, "mamba_d_state"),
    ({"mamba_d_conv": 1}, "mamba_d_conv"),
    ({"mamba_layout": (1, 0)}, "mamba_layout"),
    ({"cross_from": 5}, "full-attention layer"),
    ({"cross_from": N_LAYERS}, "cross_from"),
    ({"n_kv_heads": 3, "n_heads": 6}, "diff_attn"),
    ({"pos_type": "rope"}, "diff_attn"),
    ({"num_experts": 4, "moe_router": "topk"}, "mamba_layout"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_config_is_validated(change, match):
    with pytest.raises(ValueError, match=match):
        T._validate_config(TransformerConfig(**dict(MODEL, **change)))


def test_model_has_pages_rings_and_rows_and_no_dense_strip(model):
    params, cfg = model
    with pytest.raises(ValueError, match="state rows"):
        init_kv_cache(cfg, 1)
    with pytest.raises(ValueError, match="state rows"):
        init_kv_pages(cfg, (8, 8), PAGE)
    kp, vp, full, rings = _pools(cfg, 1)
    with pytest.raises(ValueError, match="LinearStateCache"):
        transformer_decode_step(
            params, paged_cache(kp[0], vp[0], (full, rings), PAGE),
            jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32), cfg)
