"""Continuous batching + paged KV-cache decode serving (ISSUE 9).

Acceptance: N concurrent /generate clients with heterogeneous
prompt/output lengths through a warmed DecodeEngine produce token
streams BITWISE-identical to per-request unbatched
transformer_decode_step decode, with zero XLA compiles after warmup and
a jit cache bounded by len(prefill buckets) + len(slot buckets); a
short request admitted while a long one is mid-decode finishes without
waiting for it. Plus: the page-allocator invariants, the decode.step
fault point (a mid-decode crash retires slots and frees pages), the
paged-vs-dense numeric contract, and the ragged dense-cache fix.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, telemetry as tm, tracing as tr
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import (DeadlineExceededError, DecodeConfig,
                             DecodeEngine, EngineClosedError, PagePool,
                             PagePoolExhausted, QueueFullError, serve_http)
from mxnet_tpu.serve.kv_pages import NULL_PAGE, pages_needed

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from mxnet_tpu.parallel.transformer import (  # noqa: E402
    PagedKVCache, TransformerConfig, init_kv_cache, init_kv_pages,
    init_transformer_params, transformer_decode_step,
    transformer_prefill, transformer_prefill_paged)

MAX_CTX = 32
PAGE = 4


@pytest.fixture(scope="module")
def model():
    """Tiny GQA+RoPE transformer shared by every test (params,
    TransformerConfig)."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_len=64, pos_type="rope")
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=11)
    return params, cfg


@pytest.fixture(scope="module")
def engine(model):
    """One warmed shared engine (slots=4, 4-token pages)."""
    params, cfg = model
    dcfg = DecodeConfig(slots=4, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=8,
                        max_new_tokens=16, default_timeout_ms=60000)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    yield eng
    eng.close()


def reference_decode(params, cfg, prompt, max_new):
    """Per-request UNBATCHED greedy decode: dense-cache
    transformer_prefill + transformer_decode_step, b=1 — the bitwise
    ground truth the continuous batcher must reproduce."""
    dc = init_kv_cache(cfg, 1, max_len=MAX_CTX)
    logits, dc = transformer_prefill(
        params, jnp.asarray([prompt], jnp.int32), dc, cfg)
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(out) < max_new:
        logits, dc = transformer_decode_step(
            params, dc, jnp.asarray([out[-1]], jnp.int32), pos, cfg)
        out.append(int(jnp.argmax(logits[0])))
        pos += 1
    return out


# ---------------------------------------------------------------------------
# page allocator invariants
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_roundtrip():
    pool = PagePool(16)
    assert pool.capacity == 15           # page 0 reserved (null page)
    a = pool.alloc(5)
    b = pool.alloc(7)
    assert len(set(a) | set(b)) == 12    # never double-assigned
    assert NULL_PAGE not in a and NULL_PAGE not in b
    assert pool.free_pages == 3
    pool.free(a)
    assert pool.free_pages == 8          # exactly a's pages returned
    pool.free(b)
    assert pool.free_pages == 15
    assert pool.used_pages == 0


def test_page_pool_never_hands_out_held_pages():
    pool = PagePool(8)
    seen = set()
    held = [pool.alloc(2) for _ in range(3)]
    for ids in held:
        for p in ids:
            assert p not in seen
            seen.add(p)
    pool.free(held[1])
    again = pool.alloc(2)
    assert set(again) == set(held[1])    # only the freed pages recycle


def test_page_pool_exhaustion_raises_not_hangs():
    pool = PagePool(4)
    pool.alloc(3)
    t0 = time.monotonic()
    with pytest.raises(PagePoolExhausted) as ei:
        pool.alloc(1)
    assert time.monotonic() - t0 < 1.0   # synchronous, no wait
    assert "page" in str(ei.value)
    # PagePoolExhausted rides the existing 503 admission path
    assert isinstance(ei.value, QueueFullError)


def test_page_pool_double_free_raises():
    pool = PagePool(8)
    ids = pool.alloc(2)
    pool.free(ids)
    with pytest.raises(MXNetError):
        pool.free(ids)
    with pytest.raises(MXNetError):
        pool.free([NULL_PAGE])


def test_pages_needed():
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2
    assert pages_needed(32, 4) == 8


# ---------------------------------------------------------------------------
# cache-layout contract: dense ragged + paged == dense
# ---------------------------------------------------------------------------

def assert_same_logits(got, want, ulps=16):
    """Two programs that compute the same logits from the same cached
    values, to ``ulps`` units in the last place OF THE LARGEST LOGIT.

    Not bitwise: the two sides are different XLA programs (batch 2
    against batch 1, a page gather against a dense strip, a bucket of
    16 against a prompt of 6), and XLA:CPU picks the dot kernels and
    their summation order by shape, so the float32 results differ in
    the lowest bit or two — on this sandbox's CPU the old byte
    comparison stopped at one ulp (0x3da99fcf against 0x3da99fd0).
    What the contract guards — no row reads a neighbour's, a stale or a
    padded position — moves a logit by 1e-3 and more, a thousand times
    this bound; bitwise equality is kept where both sides ARE one
    program (the engine's streams against unbatched decode, below)."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    bound = ulps * np.spacing(np.abs(want).max())
    assert np.abs(got - want).max() <= bound, (
        np.abs(got - want).max(), bound)


def test_dense_decode_per_row_positions_bitwise(model):
    """Satellite: the dense cache takes per-row cur_len — a ragged
    batch's rows are bitwise what each row computes alone at b=1 (no
    row attends past its own length)."""
    params, cfg = model
    rng = np.random.RandomState(0)
    hist = jnp.asarray(rng.randint(0, 64, (2, 6)), jnp.int32)
    c2 = init_kv_cache(cfg, 2, max_len=MAX_CTX)
    # row 0 is 3 tokens deep, row 1 is 5 tokens deep
    depths = [3, 5]
    for t in range(5):
        step_pos = jnp.asarray([min(t, depths[0] - 1), t], jnp.int32)
        toks = jnp.stack([hist[0, min(t, depths[0] - 1)], hist[1, t]])
        _, c2 = transformer_decode_step(params, c2, toks, step_pos, cfg)
    probe = hist[:, 5]
    l2, _ = transformer_decode_step(
        params, c2, probe, jnp.asarray(depths, jnp.int32), cfg)
    for r, depth in enumerate(depths):
        c1 = init_kv_cache(cfg, 1, max_len=MAX_CTX)
        for t in range(depth):
            _, c1 = transformer_decode_step(params, c1,
                                            hist[r:r + 1, t], t, cfg)
        l1, _ = transformer_decode_step(params, c1, probe[r:r + 1],
                                        depth, cfg)
        assert_same_logits(np.asarray(l2)[r], np.asarray(l1)[0])


def test_paged_decode_matches_dense_bitwise(model):
    """Paged prefill + paged decode == dense prefill + dense decode,
    token logits bitwise, when the block table addresses the same
    context width."""
    params, cfg = model
    rng = np.random.RandomState(7)
    s = 5
    prompt = jnp.asarray(rng.randint(0, 64, (1, s)), jnp.int32)

    dc = init_kv_cache(cfg, 1, max_len=MAX_CTX)
    l_ref, dc = transformer_prefill(params, prompt, dc, cfg)

    kp, vp = init_kv_pages(cfg, 16, PAGE)
    bt = jnp.asarray(np.arange(1, 1 + MAX_CTX // PAGE,
                               dtype=np.int32)[None])
    paged = PagedKVCache(kp, vp, bt, PAGE)
    padded = jnp.concatenate(
        [prompt, jnp.zeros((1, 8 - s), jnp.int32)], 1)
    l_pg, paged = transformer_prefill_paged(
        params, paged, padded, jnp.asarray([s], jnp.int32), cfg)
    assert_same_logits(l_pg, l_ref)

    tok = jnp.asarray([int(jnp.argmax(l_ref[0]))], jnp.int32)
    pos = s
    for _ in range(4):
        ld, dc = transformer_decode_step(params, dc, tok, pos, cfg)
        lp, paged = transformer_decode_step(
            params, paged, tok, jnp.asarray([pos], jnp.int32), cfg)
        assert_same_logits(lp, ld)
        tok = jnp.asarray([int(jnp.argmax(ld[0]))], jnp.int32)
        pos += 1


def test_prefill_bucket_padding_is_invisible(model):
    """Prompt padded to a larger prefill bucket produces bitwise the
    unpadded logits (causality + the kpos mask keep the tail out)."""
    params, cfg = model
    rng = np.random.RandomState(3)
    s = 6
    prompt = jnp.asarray(rng.randint(0, 64, (1, s)), jnp.int32)
    dc = init_kv_cache(cfg, 1, max_len=MAX_CTX)
    l_ref, _ = transformer_prefill(params, prompt, dc, cfg)
    kp, vp = init_kv_pages(cfg, 16, PAGE)
    bt = jnp.asarray(np.arange(1, 1 + MAX_CTX // PAGE,
                               dtype=np.int32)[None])
    padded = jnp.concatenate(
        [prompt, jnp.zeros((1, 16 - s), jnp.int32)], 1)   # bucket 16
    l_pg, _ = transformer_prefill_paged(
        params, PagedKVCache(kp, vp, bt, PAGE), padded,
        jnp.asarray([s], jnp.int32), cfg)
    assert_same_logits(l_pg, l_ref)


def _walk_case(kvh, g, hd, ps, n_entries, lengths, layers=None, seed=5):
    """q, pools, a block table whose rows own distinct pages (the entries
    past a row's live prefix name the null page 0) and the lengths."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    live = [-(-n // ps) for n in lengths]
    n_pages = 1 + sum(live)
    shape = (n_pages, ps, kvh, hd) if layers is None \
        else (layers, n_pages, ps, kvh, hd)
    q = jnp.asarray(rng.randn(b, kvh, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    vp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    bt = np.zeros((b, n_entries), np.int32)
    pages = iter(1 + rng.permutation(n_pages - 1))
    for r, n in enumerate(live):
        bt[r, :n] = [next(pages) for _ in range(n)]
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)


# (kv_heads, group, head_dim, page_size, table entries, lengths, layers,
# layer): the three served cells' shapes at small page counts. In float32
# a block holds P = 4 pages at 16 heads of 128, 16 at 4 heads of 128, one
# at pages of 512: lengths 1 (a dummy slot), around a page's and a block's
# end, the full table
_WALK_CASES = {
    "two_rows": (2, 2, 8, 4, 2, [5, 7], None, None),
    "chat_16x1x128": (16, 1, 128, 16, 20,
                      [1, 15, 16, 17, 63, 64, 65, 320], None, None),
    "chat_one_row": (16, 1, 128, 16, 20, [200], None, None),
    "chat_dummy_rows": (16, 1, 128, 16, 20, [1, 1, 300, 1], None, None),
    "chat_last_layer": (16, 1, 128, 16, 20, [129, 1, 16], 3, 2),
    "mixed_4x7x128": (4, 7, 128, 16, 40,
                      [1, 15, 16, 17, 255, 256, 257, 640], None, None),
    "mixed_last_layer": (4, 7, 128, 16, 40, [513, 1], 2, 1),
    "long_2x8x256_page512": (2, 8, 256, 512, 4,
                             [1, 511, 512, 513, 2048], None, None),
    "long_last_layer": (2, 8, 256, 512, 4, [1025, 1], 3, 2),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_attention_kernel_matches_xla_twin(case):
    """The Pallas paged decode-attention kernel (interpret mode) agrees
    with its pure-lax gather twin — same contract the TPU path runs."""
    from mxnet_tpu.ops.pallas.flash_attention import (
        _paged_decode_xla, paged_decode_attention)
    kvh, g, hd, ps, n_entries, lengths, layers, layer = _WALK_CASES[case]
    q, kp, vp, bt, ln = _walk_case(kvh, g, hd, ps, n_entries, lengths,
                                   layers)
    ref = _paged_decode_xla(q, kp, vp, bt, ln, 1 / np.sqrt(hd), layer=layer)
    got = paged_decode_attention(q, kp, vp, bt, ln, interpret=True,
                                 layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape,want", [
    ((16, 16, 128, 2, 128), 8),       # decode_chat: pages of 64 KB
    ((16, 4, 128, 2, 1024), 16),      # decode_mixed_len: 16 KB, global
    ((16, 4, 128, 2, 257), 16),       # ... and its rings
    ((512, 2, 256, 2, 32), 1),        # decode_long_answers: 512 KB
    ((16, 4, 128, 2, 5), 5),          # never more than the table holds
    ((16, 2, 8, 4, 64), 16),          # the interpreter's small heads
    ((16, 16, 128, 4, 20), 4),        # float32 pages are twice the bytes
])
def test_pages_per_block_follows_the_page_bytes(shape, want):
    """``P`` is a function of the operands' shapes alone: a block of up
    to 256 tokens whose four buffers fit the VMEM budget."""
    from mxnet_tpu.ops.pallas.flash_attention import (
        _PAGED_VMEM_BUDGET, _pages_per_block)
    ps, kvh, hd, itemsize, n_entries = shape
    got = _pages_per_block(*shape)
    assert got == want
    assert 4 * got * ps * kvh * hd * itemsize \
        <= max(_PAGED_VMEM_BUDGET, 4 * ps * kvh * hd * itemsize)


@pytest.mark.parametrize("kvh,hd,itemsize,want", [
    (16, 128, 2, True), (4, 128, 2, True), (2, 256, 2, True),   # the cells
    (24, 128, 2, True), (8, 128, 2, True),
    (1, 128, 2, False), (3, 128, 2, False), (12, 128, 2, False),
    (2, 64, 2, False), (2, 64, 4, False),         # a head under a lane tile
    (1, 128, 4, True), (5, 128, 4, True),         # float32: any head count
])
def test_pools_the_decode_walk_cannot_copy_pages_of_get_the_twin(
        kvh, hd, itemsize, want):
    """Mosaic slices an HBM ref by whole memory tiles: the kernel runs
    where a page's (kv_heads, head_dim) fills them, the twin elsewhere
    (``tools/check_mosaic_aot.py`` compiles both sides for the v5e)."""
    from mxnet_tpu.ops.pallas.flash_attention import _pages_can_be_copied
    assert _pages_can_be_copied(kvh, hd, itemsize) is want


@pytest.mark.parametrize("window", [None, 64])
def test_paged_attention_kernel_reads_live_pages_only(window):
    """The walk copies the pages a row's live prefix names and no other:
    with every other page of the pool NaN (the null page, the pages no
    row owns and, under a window, the ring entries that fell out of it)
    the kernel's output is finite and equals the twin's on the clean
    pool."""
    from mxnet_tpu.ops.pallas.flash_attention import (
        _paged_decode_xla, paged_decode_attention)
    rng = np.random.RandomState(11)
    kvh, g, hd, ps = 4, 7, 128, 16
    n_entries = 12 if window is None else 9
    lengths = [1, 17, 100, 192] if window is None else [1, 60, 150, 1000]
    b, n_pages = len(lengths), 1 + len(lengths) * n_entries + 3
    q = jnp.asarray(rng.randn(b, kvh, g, hd).astype(np.float32))
    kp = rng.randn(2, n_pages, ps, kvh, hd).astype(np.float32)
    vp = rng.randn(2, n_pages, ps, kvh, hd).astype(np.float32)
    bt = 1 + np.arange(b * n_entries, dtype=np.int32).reshape(b, n_entries)
    live = np.zeros(n_pages, bool)
    for r, n in enumerate(lengths):
        a_last = (n - 1) // ps
        if window is None:
            bt[r, a_last + 1:] = 0           # unwritten entries: null page
            live[bt[r, :a_last + 1]] = True
        else:
            first = max(0, (n - window) // ps, a_last - n_entries + 1)
            for a in range(first, a_last + 1):
                live[bt[r, a % n_entries]] = True
    ln = jnp.asarray(lengths, jnp.int32)
    want = _paged_decode_xla(q, jnp.asarray(kp), jnp.asarray(vp),
                             jnp.asarray(bt), ln, 1 / np.sqrt(hd), window,
                             layer=1)
    kp[:, ~live] = np.nan
    vp[:, ~live] = np.nan
    kp[0], vp[0] = np.nan, np.nan            # and every other layer
    got = np.asarray(paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), ln,
        interpret=True, window=window, layer=1))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_kernel_reads_its_layer_of_a_whole_pool(layer):
    """Given the pool of every layer and a layer's index, the kernel
    reads that layer's pages: it agrees with its twin and is bitwise the
    call over the layer's own 4-D pool."""
    from mxnet_tpu.ops.pallas.flash_attention import (
        _paged_decode_xla, paged_decode_attention)
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(2, 2, 2, 8).astype(np.float32))
    kp = jnp.asarray(rng.randn(3, 8, 4, 2, 8).astype(np.float32))
    vp = jnp.asarray(rng.randn(3, 8, 4, 2, 8).astype(np.float32))
    bt = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    ln = jnp.asarray(np.array([5, 7], np.int32))
    ref = _paged_decode_xla(q, kp, vp, bt, ln, 1 / np.sqrt(8), layer=layer)
    got = paged_decode_attention(q, kp, vp, bt, ln, interpret=True,
                                 layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    one = paged_decode_attention(q, kp[layer], vp[layer], bt, ln,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    other = paged_decode_attention(q, kp, vp, bt, ln, interpret=True,
                                   layer=1)
    assert not np.allclose(np.asarray(got), np.asarray(other))
    # off the TPU the default dispatch is the twin, layer and all
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(q, kp, vp, bt, ln, layer=layer)),
        np.asarray(ref))


# ---------------------------------------------------------------------------
# engine acceptance
# ---------------------------------------------------------------------------

def test_continuous_batching_bitwise_zero_compiles(model, engine):
    """ACCEPTANCE: concurrent clients with heterogeneous prompt/output
    lengths through the warmed engine get streams bitwise-identical to
    per-request unbatched transformer_decode_step decode, with ZERO
    XLA compiles after warmup and the jit cache bounded by
    len(prefill buckets) + len(slot buckets)."""
    params, cfg = model
    rng = np.random.RandomState(5)
    reqs = [(list(rng.randint(0, 64, (pl,))), mn) for pl, mn in
            [(3, 6), (7, 10), (12, 4), (5, 12), (9, 2), (16, 8),
             (2, 16), (11, 5)]]
    compiles0 = tm.snapshot()["backend_compile_total"]
    results = [None] * len(reqs)

    def client(i):
        p, mn = reqs[i]
        results[i] = engine.submit(p, mn).result()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tm.snapshot()["backend_compile_total"] == compiles0
    bound = (len(engine.config.prefill_buckets)
             + len(engine.config.slot_buckets))
    assert engine.program_count() <= bound
    for i, (p, mn) in enumerate(reqs):
        assert results[i] == reference_decode(params, cfg, p, mn), \
            "stream %d diverged from unbatched decode" % i
    # every reservation returned to the pool
    assert engine._pool.used_pages == 0


def test_short_request_overtakes_long(model, engine):
    """A short request admitted while a long one is mid-decode finishes
    first — iteration-level scheduling, not batch-at-admission.

    Event-driven, not timing-driven: the scheduler iteration hook
    parks the loop on a semaphore, so the short request is PROVABLY
    submitted while the long one is mid-decode (two tokens in, 14 to
    go) no matter how loaded the host is — the historical flake here
    was the free-running scheduler finishing the long request before a
    starved client thread got the short one admitted."""
    gate = threading.Semaphore(0)
    # armed while the scheduler idles INSIDE an iteration (its wait
    # loop), so the first iteration with work runs without a permit and
    # the loop then parks at the next iteration boundary
    engine.set_iteration_hook(gate.acquire)
    try:
        long_sess = engine.submit(list(range(4)), max_new_tokens=16)
        # iteration 1: admit + prefill (token 1) + step (token 2), then
        # the scheduler parks — the long request CANNOT advance
        assert long_sess.next_token(timeout=30) is not None
        assert long_sess.next_token(timeout=30) is not None
        assert not long_sess.done
        # mid-decode by construction: submit the short request while
        # the scheduler is parked, then free-run
        short_sess = engine.submit(list(range(5, 8)), max_new_tokens=2)
        engine.set_iteration_hook(None)
        gate.release()                   # unpark the waiting acquire
        short = short_sess.result()
        assert len(short) == 2
        long_out = long_sess.result()
        assert len(long_out) == 16
        assert short_sess.t_done < long_sess.t_done
    finally:
        engine.set_iteration_hook(None)
        gate.release(4)                  # never leave the loop parked


def test_admission_rejects_oversized_and_bad_tokens(engine):
    with pytest.raises(MXNetError):
        engine.submit([])
    with pytest.raises(MXNetError):
        engine.submit([99])              # vocab is 64
    with pytest.raises(MXNetError):
        engine.submit(list(range(40)))   # beyond the prefill ladder
    with pytest.raises(MXNetError):
        engine.submit(list(range(30)), max_new_tokens=10)  # > max_context


def test_page_exhaustion_is_distinct_503(model):
    """Page exhaustion refuses through the QueueFullError path but
    names pages, distinct from queue-depth rejection."""
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=3,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=16)
    eng = DecodeEngine(params, cfg, dcfg)   # never started: queue holds
    try:
        with pytest.raises(PagePoolExhausted) as ei:
            eng.submit(list(range(9)), max_new_tokens=8)  # needs 5 pages
        assert "page" in str(ei.value)
        assert tm.snapshot()["decode_rejected"] >= 1
    finally:
        eng.close(drain=False)


def test_queue_depth_rejection(model):
    params, cfg = model
    dcfg = DecodeConfig(slots=1, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=2,
                        max_new_tokens=4)
    eng = DecodeEngine(params, cfg, dcfg)   # not started: requests park
    try:
        eng.submit([1], max_new_tokens=1)
        eng.submit([2], max_new_tokens=1)
        with pytest.raises(QueueFullError) as ei:
            eng.submit([3], max_new_tokens=1)
        assert "queue" in str(ei.value)
        assert not isinstance(ei.value, PagePoolExhausted)
    finally:
        eng.close(drain=False)


def test_deadline_mid_decode_retires_and_frees(model):
    """A session whose deadline expires mid-stream is retired: the
    client sees DeadlineExceededError, its slot frees, its pages return
    to the pool."""
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=16)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        # slow every scheduler iteration so the deadline reliably
        # expires mid-stream regardless of host speed
        with fault.arming("decode.step", step=1, kind="delay",
                          count=10**6, delay_ms=60):
            sess = eng.submit([1, 2, 3], max_new_tokens=16,
                              timeout_ms=200)
            with pytest.raises(DeadlineExceededError):
                while sess.next_token(timeout=10) is not None:
                    pass
        deadline = time.monotonic() + 10
        while eng._pool.used_pages and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng._pool.used_pages == 0
        assert tm.snapshot()["decode_timeouts"] >= 1
    finally:
        eng.close(drain=False)


def test_decode_step_fault_retires_slots_and_frees_pages(model):
    """Fault point decode.step: a mid-decode scheduler crash fails the
    live sessions, frees their pages, and the restarted loop keeps
    serving new requests."""
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=8)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    preempted0 = tm.snapshot()["decode_preempted"]
    try:
        with fault.arming("decode.step", step=3, kind="raise"):
            sess = eng.submit([1, 2, 3], max_new_tokens=8)
            with pytest.raises(MXNetError):
                sess.result()
        assert fault.hits("decode.step") >= 3
        assert eng._pool.used_pages == 0           # pages came back
        assert tm.snapshot()["decode_preempted"] > preempted0
        # the restarted scheduler still serves, bitwise-correct
        out = eng.generate([4, 5], max_new_tokens=3)
        assert out == reference_decode(params, cfg, [4, 5], 3)
    finally:
        eng.close(drain=False)


def test_swap_params_drains_then_serves_new_weights(model):
    """DecodeEngine.swap_params: sessions drain, weights rotate with
    zero recompiles, and post-swap output matches the new weights'
    unbatched reference."""
    params, cfg = model
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params2, _ = init_transformer_params(cfg, mesh, seed=99)
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=8)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        sess = eng.submit([1, 2, 3], max_new_tokens=6)
        compiles0 = tm.snapshot()["backend_compile_total"]
        eng.swap_params(params2)
        # the in-flight session finished (on the old weights) before
        # the swap returned
        assert sess.done
        assert sess.error is None
        assert tm.snapshot()["backend_compile_total"] == compiles0
        out = eng.generate([7, 8], max_new_tokens=4)
        assert out == reference_decode(params2, cfg, [7, 8], 4)
    finally:
        eng.close(drain=False)


# ---------------------------------------------------------------------------
# HTTP /generate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def http_srv(engine):
    srv = serve_http(None, decode=engine)
    yield srv
    srv.close()


def _post_generate(url, payload, rid=None, timeout=30):
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["X-Request-Id"] = rid
    req = urllib.request.Request(url + "/generate",
                                 data=json.dumps(payload).encode(),
                                 headers=headers, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return (r.status, r.read().decode(), dict(r.headers))
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def test_http_generate_streams_tokens(model, engine, http_srv):
    params, cfg = model
    prompt = [1, 2, 3, 4]
    status, body, headers = _post_generate(
        http_srv.url, {"prompt": prompt, "max_new_tokens": 5},
        rid="gen-trace-1")
    assert status == 200
    assert headers.get("X-Request-Id") == "gen-trace-1"
    lines = [json.loads(l) for l in body.strip().split("\n")]
    assert lines[-1] == {"done": True, "n": 5}
    toks = [l["token"] for l in lines[:-1]]
    assert toks == reference_decode(params, cfg, prompt, 5)
    # the request trace carries the decode-phase spans, serve.batch-style
    trace = tr.get_trace("gen-trace-1")
    assert trace is not None
    names = {s["name"] for s in trace["spans"]}
    assert {"http.request", "decode.prefill", "decode.step",
            "decode.schedule"} <= names


def test_http_generate_nonstream_and_healthz(model, engine, http_srv):
    params, cfg = model
    status, body, _ = _post_generate(
        http_srv.url, {"prompt": [9, 8], "max_new_tokens": 3,
                       "stream": False})
    assert status == 200
    payload = json.loads(body)
    assert payload["n"] == 3
    assert payload["tokens"] == reference_decode(params, cfg, [9, 8], 3)
    with urllib.request.urlopen(http_srv.url + "/healthz",
                                timeout=10) as r:
        assert r.status == 200


def test_http_generate_400_on_bad_input(http_srv):
    status, body, _ = _post_generate(http_srv.url, {"prompt": "oops"})
    assert status == 400
    status, body, _ = _post_generate(http_srv.url, {"nope": 1})
    assert status == 400


def test_registry_swap_drains_decode_sessions(model, tmp_path):
    """ModelRegistry.swap with an attached decode engine drains decode
    sessions BEFORE the hot-swap, rotates the decode weights passed as
    decode_params inside the quiesced window, and /generate keeps
    working after."""
    from mxnet_tpu.serve import ModelRegistry, ServeConfig
    params, cfg = model
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params2, _ = init_transformer_params(cfg, mesh, seed=77)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(0)
    pfile = str(tmp_path / "m.params")
    mx.nd.save(pfile, {
        "arg:fc_weight": mx.nd.array(
            rng.randn(3, 4).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(np.zeros(3, np.float32))})
    with open(pfile, "rb") as f:
        blob = f.read()
    reg = ModelRegistry(sym.tojson(), blob,
                        input_shapes={"data": (1, 4)},
                        config=ServeConfig(max_batch=2, queue_depth=8))
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=16)
    eng = reg.attach_decode(
        DecodeEngine(params, cfg, dcfg).start().warmup())
    try:
        reg.warmup()
        sess = eng.submit([1, 2], max_new_tokens=8)
        reg.swap(blob, decode_params=params2)
        # the decode session drained before the flip — and finished on
        # the weights it started with
        assert sess.done and sess.error is None
        assert sess.result() == reference_decode(params, cfg, [1, 2], 8)
        # admission re-opened, now serving the rotated decode weights
        assert eng.generate([3], max_new_tokens=2) == \
            reference_decode(params2, cfg, [3], 2)
        assert tm.snapshot()["serve_swaps"] >= 1
    finally:
        reg.close(drain=False)


def test_cancel_frees_slot_and_pages(model):
    """Cancelling a live session ends its stream with an error, frees
    its slot and pages (scheduler-swept), and the engine keeps
    serving."""
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=16)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    try:
        with fault.arming("decode.step", step=1, kind="delay",
                          count=10**6, delay_ms=20):
            sess = eng.submit([1, 2, 3], max_new_tokens=16)
            assert sess.next_token(timeout=30) is not None
            assert eng.cancel(sess, "test")
            with pytest.raises(MXNetError):
                sess.result()
            assert not eng.cancel(sess)          # already done
        deadline = time.monotonic() + 10
        while eng._pool.used_pages and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng._pool.used_pages == 0
        out = eng.generate([4, 5], max_new_tokens=2)
        assert out == reference_decode(params, cfg, [4, 5], 2)
    finally:
        eng.close(drain=False)


def test_http_client_disconnect_cancels_session(model):
    """A streaming /generate client that drops its connection frees
    the session's slot and pages well before the deadline."""
    import socket
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=16, default_timeout_ms=120000)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    srv = serve_http(None, decode=eng)
    try:
        with fault.arming("decode.step", step=1, kind="delay",
                          count=10**6, delay_ms=30):
            body = json.dumps({"prompt": [1, 2, 3],
                               "max_new_tokens": 16}).encode()
            sock = socket.create_connection(("127.0.0.1", srv.port),
                                            timeout=10)
            sock.sendall(b"POST /generate HTTP/1.1\r\n"
                         b"Host: x\r\nContent-Type: application/json\r\n"
                         b"Content-Length: %d\r\n\r\n" % len(body) + body)
            sock.recv(256)               # status line + first bytes
            # hard drop: RST on close with unread data
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            b"\x01\x00\x00\x00\x00\x00\x00\x00")
            sock.close()
            deadline = time.monotonic() + 30
            while eng._pool.used_pages and time.monotonic() < deadline:
                time.sleep(0.05)
        assert eng._pool.used_pages == 0
    finally:
        srv.close()
        eng.close(drain=False)


def test_engine_close_drain_completes_sessions(model):
    params, cfg = model
    dcfg = DecodeConfig(slots=2, page_size=PAGE, num_pages=64,
                        max_context=MAX_CTX, queue_depth=4,
                        max_new_tokens=4)
    eng = DecodeEngine(params, cfg, dcfg).start().warmup()
    sessions = [eng.submit([i + 1], max_new_tokens=4) for i in range(3)]
    eng.close(drain=True)
    for sess in sessions:
        assert len(sess.result()) == 4
    with pytest.raises(EngineClosedError):
        eng.submit([1])


def test_decode_config_validation():
    with pytest.raises(MXNetError):
        DecodeConfig(page_size=5, max_context=32)   # not a multiple
    with pytest.raises(MXNetError):
        DecodeConfig(slots=0)
    cfgd = DecodeConfig(slots=8, page_size=4, max_context=24)
    assert cfgd.prefill_buckets == (4, 8, 16, 24)
    assert cfgd.slot_buckets == (1, 2, 4, 8)
    assert cfgd.pages_per_seq == 6
