"""A model with sliding-window (rotary) layers beside global (NoPE) ones,
RMSNorm, an untied head and a drop-free top-k mixture of gated experts
whose router reads the layer's input — SmallThinker's block — through the
framework's normal paths at a small size, against the benchmark's plain
reference (``bench/reference/smallthinker_21b_a3b.py``: float32, no
cache, no kernels, no sorting). Contexts run to five windows, so every
window layer's ring of pages wraps more than once.
"""
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from mxnet_tpu.ops.pallas.flash_attention import (  # noqa: E402
    _flash_prefill_xla, _paged_decode_xla, flash_prefill_paged,
    paged_decode_attention)
from mxnet_tpu.ops.pallas.moe_ffn import (  # noqa: E402
    _moe_grouped_ffn_xla, moe_grouped_ffn)
from mxnet_tpu.parallel.moe import (  # noqa: E402
    moe_ffn_sorted, sorted_dispatch, top_k_routing)
from mxnet_tpu.parallel.transformer import (  # noqa: E402
    HybridKVCache, PagedKVCache, TransformerConfig, init_kv_cache,
    init_kv_pages, init_transformer_params, kv_layer_kinds,
    make_transformer_train_step, transformer_decode_step,
    transformer_forward_single, transformer_prefill,
    transformer_prefill_paged)
from mxnet_tpu.serve import (DecodeConfig, DecodeEngine, PagePool,  # noqa: E402
                             PagePoolExhausted)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, PAGE, RING = 8, 4, 8 // 4 + 1
CONTEXT = 5 * WINDOW + 8                 # the ring wraps three times over
TOL = 1e-4

MODEL = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=32, n_layers=8, d_ff=48, max_len=64, num_experts=8,
             moe_top_k=3, pos_type="rope", rope_base=1.5e6, norm="rmsnorm",
             norm_eps=1e-6, tie_embeddings=False,
             moe_router="topk", moe_router_input="layer",
             sliding_window=WINDOW, window_layout=[0, 1, 1, 1] * 2,
             rope_layout=[0, 1, 1, 1] * 2)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_smallthinker", os.path.join(
            ROOT, "bench", "reference", "smallthinker_21b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(**MODEL)
    params, _ = init_transformer_params(cfg, _mesh(), seed=3)
    return params, cfg


@pytest.fixture(scope="module")
def sequence(model):
    """One seeded sequence and the reference's logits and expert sets."""
    params, _cfg = model
    tokens = np.random.RandomState(0).randint(0, 256, CONTEXT)
    logits, experts = REF.forward(params, tokens, MODEL)
    return tokens, np.asarray(logits), np.asarray(experts)


def test_layer_kinds_follow_the_layouts(model):
    _params, cfg = model
    assert kv_layer_kinds(cfg) == ("full", "window", "window", "window") * 2
    assert (cfg.d_model // cfg.n_heads) != cfg.head_dim   # a head of its own


def test_forward_single_matches_reference(model, sequence):
    params, cfg = model
    tokens, want, want_experts = sequence
    got, stats = transformer_forward_single(
        params, jnp.asarray(tokens[None]), cfg, with_stats=True)
    assert np.abs(np.asarray(got)[0] - want).max() <= TOL
    assert np.array_equal(                   # (L, k, n): choice-major
        np.sort(np.asarray(stats["moe_experts"]).transpose(0, 2, 1), -1),
        np.sort(want_experts, -1))
    assert stats["moe_active_experts"].shape == (cfg.n_layers,)


@pytest.mark.parametrize("change", [
    {"sliding_window": None, "window_layout": None},    # no window mask
    {"rope_layout": None},                              # rotate every layer
    {"moe_router_input": "ffn"},                        # a late router
], ids=["window_mask", "nope_rule", "early_router"])
def test_the_comparison_sees_a_wrong_rule(model, sequence, change):
    """What the tolerance is worth: each rule of the block, changed in
    the program alone, moves the logits by 20 tolerances and more (the
    rotation least: under normal(0, 0.02) weights the scores are small
    and attention is nearly uniform, so positions matter little)."""
    params, _cfg = model
    tokens, want, _experts = sequence
    wrong = TransformerConfig(**dict(MODEL, **change))
    got = transformer_forward_single(params, jnp.asarray(tokens[None]),
                                     wrong)
    assert np.abs(np.asarray(got)[0] - want).max() > 20 * TOL


def _teacher_forced(params, cfg, cache, tokens, prompt, bucket=None):
    """Prefill ``prompt`` tokens, then decode the rest one by one; the
    logits of every position from the prompt's last on."""
    if bucket is None:
        logits, cache = transformer_prefill(
            params, jnp.asarray(tokens[None, :prompt]), cache, cfg)
    else:
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
    return np.stack(rows)


def _hybrid_cache(cfg, rows, pages_per_seq):
    """Pools and tables of a HybridKVCache for ``rows`` sequences."""
    (kf, kw), (vf, vw) = init_kv_pages(
        cfg, (rows * pages_per_seq + 1, rows * RING + 1), PAGE)
    full = 1 + np.arange(rows * pages_per_seq, dtype=np.int32)
    ring = 1 + np.arange(rows * RING, dtype=np.int32)
    return HybridKVCache(
        PagedKVCache(kf, vf, jnp.asarray(full.reshape(rows, -1)), PAGE),
        PagedKVCache(kw, vw, jnp.asarray(ring.reshape(rows, -1)), PAGE))


def test_prefill_then_decode_dense_cache_matches_reference(model, sequence):
    params, cfg = model
    tokens, want, _experts = sequence
    prompt = 13
    got = _teacher_forced(params, cfg, init_kv_cache(cfg, 1, max_len=64),
                          tokens, prompt)
    assert np.abs(got - want[prompt - 1:]).max() <= TOL


@pytest.mark.parametrize("prompt,bucket", [(13, 16), (5, 8), (30, 32)])
def test_prefill_then_decode_two_kind_cache_matches_reference(
        model, sequence, prompt, bucket):
    """A ring of three pages of four under prompts of 5 (inside one
    window), 13 and 30 tokens (past the ring: the padded bucket's tail
    must not wrap onto live entries), decoded on to 48 positions."""
    params, cfg = model
    tokens, want, _experts = sequence
    got = _teacher_forced(params, cfg, _hybrid_cache(cfg, 1, 16), tokens,
                          prompt, bucket=bucket)
    assert np.abs(got - want[prompt - 1:]).max() <= TOL


def test_engine_serves_ragged_slots_as_the_reference(model):
    """Through DecodeEngine: prompts on both sides of the window, answers
    that run the ring round, slots at different depths; every generated
    token's reference logit is that position's maximum to the tolerance."""
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        slots=4, page_size=PAGE, num_pages=80, window_pages=6 * RING + 1,
        max_context=64, queue_depth=16, max_new_tokens=40,
        default_timeout_ms=120000)).start().warmup()
    try:
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 256, n).tolist(), new) for n, new in
                [(3, 30), (20, 12), (9, 40), (33, 5), (14, 25), (6, 8)]]
        sessions = [eng.submit(p, new) for p, new in reqs]
        outs = [s.result() for s in sessions]
        assert eng._pool.used_pages == 0 and eng._wpool.used_pages == 0
    finally:
        eng.close()
    for (prompt, new), out, sess in zip(reqs, outs, sessions):
        assert len(out) == new
        logits, experts = REF.forward(params, np.asarray(prompt + out),
                                      MODEL)
        rows = np.asarray(logits)[len(prompt) - 1:len(prompt) - 1 + new]
        gap = rows.max(-1) - rows[np.arange(new), np.asarray(out)]
        assert gap.max() <= TOL
        # the session carries what the programs that served it chose:
        # the prompt's positions from its prefill, then one a step (the
        # last token is never fed) — the reference's sets, every one
        assert [c.shape[1] for c in sess.expert_choices] \
            == [len(prompt)] + [1] * (new - 1)
        served = np.concatenate(sess.expert_choices, axis=1)
        assert np.array_equal(np.sort(served, -1),
                              np.sort(np.asarray(experts)[:, :-1], -1))


def test_experts_of_steps_that_go_ahead_stay_with_their_rows(model):
    """The loop that dispatches a step before it fetches the one before
    hands each session exactly the tokens and the experts ITS rows chose,
    position by position, that the synchronous loop does (an iteration
    hook installed: no step goes ahead); a row that ends by its stop token
    while the step after it is already queued gets no choice and no token
    from that dead step."""
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        slots=4, page_size=PAGE, num_pages=80, window_pages=6 * RING + 1,
        max_context=64, queue_depth=16, max_new_tokens=40,
        default_timeout_ms=120000)).start().warmup()
    rng = np.random.RandomState(11)
    reqs = [(rng.randint(0, 256, n).tolist(), new) for n, new in
            [(5, 20), (12, 9), (3, 14), (20, 6)]]

    def serve(stop, hook=None):
        eng.set_iteration_hook(hook)
        try:
            ss = [eng.submit(p, new, stop_token=stop if i == 0 else None)
                  for i, (p, new) in enumerate(reqs)]
            return [(s.result(), s.expert_choices) for s in ss]
        finally:
            eng.set_iteration_hook(None)

    from mxnet_tpu import telemetry as tm
    try:
        free = serve(None)[0][0]
        first = next(i for i in range(3, len(free))
                     if free[i] not in free[:i])
        ahead0 = tm.counter("decode/steps_ahead_total").value
        piped = serve(free[first])
        assert tm.counter("decode/steps_ahead_total").value > ahead0
        sync = serve(free[first], hook=lambda: None)
        assert eng._pool.used_pages == 0
    finally:
        eng.close()
    assert piped[0][0] == free[:first + 1]
    assert [c.shape[1] for c in piped[0][1]] \
        == [len(reqs[0][0])] + [1] * first
    for (ptoks, pexp), (stoks, sexp) in zip(piped, sync):
        assert ptoks == stoks
        assert len(pexp) == len(sexp)
        assert all(np.array_equal(a, b) for a, b in zip(pexp, sexp))


def test_engine_reports_experts_and_window_context(model):
    from mxnet_tpu import telemetry as tm, tracing as tr
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        slots=2, page_size=PAGE, num_pages=32, max_context=32,
        queue_depth=4, max_new_tokens=12)).start().warmup()
    rows0 = tm.counter("decode/moe_assignments_total").value
    try:
        eng.generate(list(range(1, 14)), 12)
    finally:
        eng.close()
    assert tm.counter("decode/moe_assignments_total").value > rows0
    steps = [r for r in tr.span_log() if r["name"] == "decode.step"
             and "moe_rows" in r["attrs"]]
    assert steps
    last = steps[-1]["attrs"]
    # one row 24 deep: a window layer sees 8 of its 24 positions
    assert last["context_tokens"] == 24
    assert last["window_context_tokens"] == WINDOW
    assert last["moe_rows"] == 1 * cfg.moe_top_k * cfg.n_layers
    assert 1 <= last["moe_active_experts"] <= last["moe_rows"]
    prefill = [r for r in tr.span_log() if r["name"] == "decode.prefill"
               and "moe_rows" in r["attrs"]][-1]["attrs"]
    # the prompt's 13 rows, not the bucket's 16: padding is no work
    assert prefill["moe_rows"] == 13 * cfg.moe_top_k * cfg.n_layers


def test_a_request_holds_pages_of_both_kinds_and_returns_both(model):
    params, cfg = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        slots=2, page_size=PAGE, num_pages=17, window_pages=RING + 2,
        max_context=32, queue_depth=4, max_new_tokens=8))
    # not started: the sessions wait, holding their reservations
    a = eng.submit(list(range(20)), 8)            # 7 pages; a ring of 3
    assert len(a.page_ids) == 7 and len(a.window_page_ids) == RING
    with pytest.raises(PagePoolExhausted) as err:
        eng.submit(list(range(20)), 8)            # the window pool: 1 left
    assert "window-layer" in str(err.value)
    assert eng._pool.used_pages == 7              # the global pages went back
    b = eng.submit([1, 2], 2)                     # 1 page of each
    assert len(b.window_page_ids) == 1
    with pytest.raises(PagePoolExhausted) as err:
        eng.submit(list(range(24)), 8)            # 8 of the 8 global left...
        eng.submit(list(range(24)), 8)
    assert "global-layer" in str(err.value) or "window-layer" in str(
        err.value)
    eng.cancel(a)
    eng.cancel(b)
    assert eng._pool.used_pages == 0 and eng._wpool.used_pages == 0
    assert PagePool(4, kind="window").kind == "window"


def test_training_block_refuses_by_field(model):
    _params, cfg = model
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))
    with pytest.raises(ValueError, match="head_dim"):
        make_transformer_train_step(cfg, mesh)
    with pytest.raises(ValueError, match="moe_router"):
        make_transformer_train_step(
            TransformerConfig(num_experts=4, moe_router="topk"), mesh)
    # and the drop-free router without experts is no model at all
    with pytest.raises(ValueError, match="num_experts"):
        transformer_forward_single(
            {}, jnp.zeros((1, 4), jnp.int32),
            TransformerConfig(moe_router="topk"))


# -- routing ---------------------------------------------------------------

def test_softmax_is_over_the_chosen_and_nothing_is_dropped():
    rng = np.random.RandomState(1)
    n, d, f, e, k = 200, 16, 24, 8, 3
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * 0.2, jnp.float32)
    # EVERY token to experts 5, 2, 7: a capacity router would drop most
    logits = jnp.asarray(rng.randn(n, e) * 0.01, jnp.float32) \
        .at[:, 5].add(9.0).at[:, 2].add(8.0).at[:, 7].add(7.5)
    experts, w = top_k_routing(logits, k)
    assert np.array_equal(np.asarray(experts), np.tile([5, 2, 7], (n, 1)))
    top = np.sort(np.asarray(logits), -1)[:, ::-1][:, :k]
    want_w = np.exp(top) / np.exp(top).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    out, _experts, active = moe_ffn_sorted(x, logits, wg, wu, wd, k)
    want = sum(want_w[:, j:j + 1] * np.asarray(
        (jax.nn.relu(x @ wg[ex]) * (x @ wu[ex])) @ wd[ex])
        for j, ex in enumerate([5, 2, 7]))
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    assert int(active) == 3
    src, dest, sizes, counts = sorted_dispatch(experts, e, 16)
    assert np.asarray(counts).tolist() == [0, 0, n, 0, 0, n, 0, n]
    assert np.asarray(sizes).tolist() == [0, 0, 208, 0, 0, 208, 0, 208]
    assert len(set(np.asarray(dest).ravel().tolist())) == n * k
    assert np.array_equal(np.asarray(src)[np.asarray(dest)],
                          np.tile(np.arange(n)[:, None], (1, k)))


# -- the kernels' windowed twins, interpret mode -----------------------------

def test_grouped_ffn_kernel_matches_its_twin():
    rng = np.random.RandomState(2)
    n, d, f, e, k, tile = 53, 32, 16, 8, 3, 16
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * 0.2, jnp.float32)
    # expert 3 gets nothing, so a group is empty
    logits = jnp.asarray(rng.randn(n, e), jnp.float32).at[:, 3].add(-50.0)
    experts, _w = top_k_routing(logits, k)
    src, _dest, sizes, counts = sorted_dispatch(experts, e, tile)
    assert int(counts[3]) == 0
    rows = x[src]
    want = _moe_grouped_ffn_xla(rows, sizes, wg, wu, wd)
    got = moe_grouped_ffn(rows, sizes, wg, wu, wd, tile, interpret=True)
    used = int(np.asarray(sizes).sum())
    assert used < rows.shape[0]          # trailing tiles are skipped
    np.testing.assert_allclose(np.asarray(got)[:used],
                               np.asarray(want)[:used],
                               rtol=2e-5, atol=2e-5)


# (rows, kv_heads, group, head_dim, page_size, ring entries, window,
# lengths): the first is the small ring of PR 26; the others SmallThinker's
# window layers' shape — 4 K/V heads serving 7 query heads of 128 — on a
# ring one compute block holds (9 entries) and one it does not (40 entries,
# blocks of 16 pages): not wrapped, filled exactly, wrapped once and many
# times, at a page's end and in its middle, a dummy slot
_RING_CASES = {
    "small": (2, 2, 8, 4, 3, 8, [5, 14, 39]),
    "ring_in_one_block": (4, 7, 128, 16, 9, 128,
                          [1, 100, 144, 145, 200, 1000, 1008]),
    "ring_of_two_blocks": (4, 7, 128, 16, 40, 624,
                           [1, 255, 256, 257, 640, 700, 5000]),
    "one_row": (4, 7, 128, 16, 9, 128, [333]),
}


@pytest.mark.parametrize("case", sorted(_RING_CASES))
def test_windowed_paged_decode_kernel_matches_its_twin(case):
    kvh, g, hd, ps, ring, window, lengths = _RING_CASES[case]
    rng = np.random.RandomState(5)
    b = len(lengths)
    q = jnp.asarray(rng.randn(b, kvh, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(b * ring + 1, ps, kvh, hd)
                     .astype(np.float32))
    vp = jnp.asarray(rng.randn(b * ring + 1, ps, kvh, hd)
                     .astype(np.float32))
    bt = jnp.asarray(1 + np.arange(b * ring, dtype=np.int32)
                     .reshape(b, ring))
    ln = jnp.asarray(np.array(lengths, np.int32))
    want = _paged_decode_xla(q, kp, vp, bt, ln, 1 / np.sqrt(hd), window)
    got = paged_decode_attention(q, kp, vp, bt, ln, interpret=True,
                                 window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if lengths[0] > min(window, ring * ps):
        return
    # and the ring's twin is the plain twin while nothing has wrapped
    plain = _paged_decode_xla(q[:1], kp, vp, bt[:1], ln[:1],
                              1 / np.sqrt(hd))
    np.testing.assert_allclose(np.asarray(want)[:1], np.asarray(plain),
                               rtol=1e-6, atol=1e-6)


def test_windowed_paged_decode_kernel_reads_a_layer_of_the_window_pool():
    """The same rings, read at layer 1 of a pool of three layers."""
    rng = np.random.RandomState(7)
    b, kvh, g, hd, ps, ring, window = 3, 2, 2, 8, 4, 3, 8
    q = jnp.asarray(rng.randn(b, kvh, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(3, 12, ps, kvh, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(3, 12, ps, kvh, hd).astype(np.float32))
    bt = jnp.asarray(1 + np.arange(b * ring, dtype=np.int32)
                     .reshape(b, ring))
    ln = jnp.asarray(np.array([5, 14, 39], np.int32))
    want = _paged_decode_xla(q, kp, vp, bt, ln, 1 / np.sqrt(hd), window,
                             layer=1)
    got = paged_decode_attention(q, kp, vp, bt, ln, interpret=True,
                                 window=window, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    one = paged_decode_attention(q, kp[1], vp[1], bt, ln, interpret=True,
                                 window=window)
    assert np.array_equal(np.asarray(got), np.asarray(one))


@pytest.mark.parametrize("hd", [8, 128])      # scatter / DMA page write
def test_windowed_prefill_kernel_writes_a_layer_of_the_window_pool(hd):
    """Rings, real lengths and a window, written at layer 2 of a pool of
    three layers; layers 0 and 1 come back untouched."""
    rng = np.random.RandomState(8)
    b, s, nh, kvh, ps, ring, window = 2, 32, 4, 2, 4, 3, 8
    q = jnp.asarray(rng.randn(b, s, nh, hd).astype(np.float32))
    kg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    vg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(3, 8, ps, kvh, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(3, 8, ps, kvh, hd).astype(np.float32))
    bt = jnp.asarray(1 + np.arange(b * ring, dtype=np.int32)
                     .reshape(b, ring))
    ln = jnp.asarray(np.array([21, 6], np.int32))
    want = _flash_prefill_xla(q, kg, vg, kp, vp, bt, ln, window, layer=2)
    got = flash_prefill_paged(q, kg, vg, kp, vp, bt, block_q=8, block_k=8,
                              interpret=True, lengths=ln, window=window,
                              layer=2)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for a, w, before in zip(got[1:], want[1:], (kp, vp)):
        # page 0 takes whatever is not kept; every real page agrees
        assert np.array_equal(np.asarray(a)[2, 1:], np.asarray(w)[2, 1:])
        assert np.array_equal(np.asarray(a)[:2], np.asarray(before)[:2])
    assert np.array_equal(np.asarray(got[1])[2, 1],
                          np.asarray(kg)[0, 12:16])
    assert np.array_equal(np.asarray(got[1])[2, 6], np.asarray(kp)[2, 6])


@pytest.mark.parametrize("hd", [8, 128])      # scatter / DMA page write
def test_windowed_prefill_kernel_matches_its_twin(hd):
    rng = np.random.RandomState(6)
    b, s, nh, kvh, ps, ring, window = 2, 32, 4, 2, 4, 3, 8
    q = jnp.asarray(rng.randn(b, s, nh, hd).astype(np.float32))
    kg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    vg = jnp.asarray(rng.randn(b, s, kvh, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(8, ps, kvh, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(8, ps, kvh, hd).astype(np.float32))
    bt = jnp.asarray(1 + np.arange(b * ring, dtype=np.int32)
                     .reshape(b, ring))
    ln = jnp.asarray(np.array([21, 6], np.int32))     # past / inside a ring
    want = _flash_prefill_xla(q, kg, vg, kp, vp, bt, ln, window)
    got = flash_prefill_paged(q, kg, vg, kp, vp, bt, block_q=8, block_k=8,
                              interpret=True, lengths=ln, window=window)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for a, w in zip(got[1:], want[1:]):
        # page 0 takes whatever is not kept; every real page agrees
        assert np.array_equal(np.asarray(a)[1:], np.asarray(w)[1:])
    # row 0 kept pages 3, 4, 5 of its 21 tokens at entries 0, 1, 2; row 1
    # its pages 0, 1 and left its third entry (pool page 6) alone
    assert np.array_equal(np.asarray(got[1])[1],
                          np.asarray(kg)[0, 12:16])
    assert np.array_equal(np.asarray(got[1])[3],
                          np.asarray(kg)[0, 20:24])
    assert np.array_equal(np.asarray(got[1])[6], np.asarray(kp)[6])
