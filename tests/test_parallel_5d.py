"""Pipeline / MoE / 5-axis transformer parallelism tests.

Every test validates the sharded computation numerically against a
single-device reference (the reference framework's check_consistency
idea, SURVEY.md §4, applied to parallelism instead of devices).

Device counts are kept ≤ 8 and models tiny: the CI host runs 8 virtual
CPU devices on very few cores.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from mxnet_tpu.parallel.moe import moe_apply, top_k_gating, \
    stack_expert_params
from mxnet_tpu.parallel.transformer import (
    TransformerConfig, init_transformer_params,
    make_transformer_train_step, transformer_forward_single)


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def _mlp_stage(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _make_stages(rng, n, dm):
    return [{"w": jnp.asarray(rng.randn(dm, dm) * 0.3, jnp.float32),
             "b": jnp.asarray(rng.randn(dm) * 0.1, jnp.float32)}
            for _ in range(n)]


def test_pipeline_forward_matches_sequential():
    mesh = make_mesh((4,), axis_names=("pp",))
    rng = np.random.RandomState(0)
    stages = _make_stages(rng, 4, 32)
    x = jnp.asarray(rng.randn(16, 32), jnp.float32)
    out = pipeline_apply(stack_stage_params(stages), x, _mlp_stage,
                         mesh=mesh, num_microbatches=8)
    ref = x
    for p in stages:
        ref = _mlp_stage(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_grads_match_sequential():
    mesh = make_mesh((4,), axis_names=("pp",))
    rng = np.random.RandomState(1)
    stages = _make_stages(rng, 4, 16)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)

    def loss_p(s):
        return jnp.sum(jnp.sin(pipeline_apply(s, x, _mlp_stage, mesh=mesh,
                                              num_microbatches=4)))

    def loss_s(ps):
        h = x
        for p in ps:
            h = _mlp_stage(p, h)
        return jnp.sum(jnp.sin(h))

    gp = jax.grad(loss_p)(stacked)
    gs = stack_stage_params(jax.grad(loss_s)(stages))
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _expert_fn_factory():
    def expert_fn(p, h):
        return jax.nn.relu(h @ p["w1"]) @ p["w2"]
    return expert_fn


def test_moe_matches_dense_routing():
    mesh = make_mesh((8,), axis_names=("ep",))
    rng = np.random.RandomState(2)
    n, d, E = 64, 16, 8
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    gate_w = jnp.asarray(rng.randn(d, E) * 0.5, jnp.float32)
    experts = [{"w1": jnp.asarray(rng.randn(d, 32) * 0.2, jnp.float32),
                "w2": jnp.asarray(rng.randn(32, d) * 0.2, jnp.float32)}
               for _ in range(E)]
    expert_fn = _expert_fn_factory()
    out, aux = moe_apply(x, gate_w, stack_expert_params(experts), expert_fn,
                         mesh=mesh, k=2, capacity_factor=4.0)
    # single-device reference with identical routing math
    C = max(1, int(4.0 * n * 2 / E))
    disp, comb, _ = top_k_gating(x @ gate_w, E, C, k=2)
    exp_in = jnp.einsum("nec,nd->ecd", disp, x)
    exp_out = jnp.stack([expert_fn(experts[e], exp_in[e]) for e in range(E)])
    ref = jnp.einsum("nec,ecd->nd", comb, exp_out)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0


def test_moe_top2_weights():
    # with generous capacity, token 0's output is the normalized top-2 mix
    mesh = make_mesh((4,), axis_names=("ep",))
    rng = np.random.RandomState(3)
    n, d, E = 32, 8, 4
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    gate_w = jnp.asarray(rng.randn(d, E), jnp.float32)
    experts = [{"w1": jnp.asarray(rng.randn(d, 16) * 0.3, jnp.float32),
                "w2": jnp.asarray(rng.randn(16, d) * 0.3, jnp.float32)}
               for _ in range(E)]
    expert_fn = _expert_fn_factory()
    out, _ = moe_apply(x, gate_w, stack_expert_params(experts), expert_fn,
                       mesh=mesh, k=2, capacity_factor=8.0)
    g = jax.nn.softmax(x[0] @ gate_w)
    i1 = int(jnp.argmax(g))
    i2 = int(jnp.argmax(g.at[i1].set(0)))
    w1 = float(g[i1] / (g[i1] + g[i2]))
    w2 = float(g[i2] / (g[i1] + g[i2]))
    manual = w1 * expert_fn(experts[i1], x[:1]) + \
        w2 * expert_fn(experts[i2], x[:1])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(manual[0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# 5-axis transformer train step
# ---------------------------------------------------------------------------

def _ref_sgd_step(cfg, params, tokens, targets, lr):
    def ref_loss(p):
        logits = transformer_forward_single(p, tokens, cfg)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return jnp.mean(nll)
    rl, rg = jax.value_and_grad(ref_loss)(params)
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, rg), rl


def _compare_step(cfg, mesh_shape, tol=5e-5, check_loss=True):
    mesh = make_mesh(mesh_shape, axis_names=("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=0)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)
    step = make_transformer_train_step(cfg, mesh, lr=0.1)
    new_params, loss = step(params, tokens, targets)
    params2, _ = init_transformer_params(cfg, mesh, seed=0)
    ref_new, rl = _ref_sgd_step(cfg, params2, tokens, targets, 0.1)
    if check_loss:  # MoE losses include the aux term, skip there
        assert abs(float(loss) - float(rl)) < 1e-5
    ref_flat = {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_leaves_with_path(ref_new)}
    for k, v in jax.tree_util.tree_leaves_with_path(new_params):
        ks = jax.tree_util.keystr(k)
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(ref_flat[ks]),
                                   rtol=1e-3, atol=tol, err_msg=ks)


_DENSE = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                           n_layers=2, d_ff=64, max_len=64)
_MOE = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=64, num_experts=4,
                         capacity_factor=8.0)


def test_transformer_dp_sp_tp():
    _compare_step(_DENSE, (2, 2, 2, 1, 1))


def test_transformer_pipeline():
    _compare_step(_DENSE, (2, 2, 1, 2, 1))


def test_transformer_sp_tp_pp():
    _compare_step(_DENSE, (1, 2, 2, 2, 1))


def test_transformer_moe_ep():
    _compare_step(_MOE, (2, 1, 1, 1, 4), tol=3e-4, check_loss=False)


def test_transformer_moe_pp_ep():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=4, d_ff=64, max_len=64, num_experts=2,
                            capacity_factor=8.0)
    _compare_step(cfg, (1, 1, 1, 2, 2), tol=3e-4, check_loss=False)


def test_transformer_ulysses_sp():
    """Same 5-axis step with the all-to-all (Ulysses) sequence-parallel
    attention instead of the ring — must match the single-device
    trajectory identically (heads_local=2 split over sp=2)."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_len=64,
                            sp_attn="ulysses")
    _compare_step(cfg, (2, 2, 2, 1, 1))


def test_transformer_remat_matches_exact():
    """remat=True must reproduce the exact same training trajectory
    (rematerialisation changes memory, not math)."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_len=64, remat=True)
    _compare_step(cfg, (2, 2, 2, 1, 1))


def test_kv_cache_decode_matches_full_forward():
    """Decode-with-cache logits equal the full causal forward at every
    position, and greedy generate matches a full-forward rollout (the
    O(1)-per-token inference path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params, init_kv_cache,
        transformer_decode_step, transformer_forward_single,
        transformer_generate)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_len=16)
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=3)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
    full = transformer_forward_single(params, tokens, cfg)

    cache = init_kv_cache(cfg, 2, max_len=16)
    for t in range(8):
        logits, cache = transformer_decode_step(
            params, cache, tokens[:, t], t, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]), rtol=2e-4,
                                   atol=2e-4)

    # greedy rollout equivalence vs repeated full forwards
    prompt = tokens[:, :4]
    gen = transformer_generate(params, prompt, steps=3, cfg=cfg)
    cur = prompt
    for _ in range(3):
        nxt = jnp.argmax(transformer_forward_single(params, cur, cfg)
                         [:, -1], axis=-1).astype(jnp.int32)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(gen),
                                  np.asarray(cur[:, 4:]))


def test_kv_cache_decode_moe():
    """The MoE FFN variant decodes through the cache path too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params, init_kv_cache,
        transformer_decode_step, transformer_forward_single)

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, d_ff=32, max_len=8,
                            num_experts=4, moe_top_k=2,
                            capacity_factor=4.0)
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=1)
    rng = np.random.RandomState(4)
    tokens = jnp.asarray(rng.randint(0, 32, (2, 5)), jnp.int32)
    cache = init_kv_cache(cfg, 2, max_len=8)
    for t in range(5):
        logits, cache = transformer_decode_step(
            params, cache, tokens[:, t], t, cfg)
    assert np.isfinite(np.asarray(logits)).all()


def test_transformer_lm_example_cli_with_generation():
    """The 5D LM example trains and then greedy-decodes through the
    KV-cache path (subprocess, as a user runs it)."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable,
         os.path.join(root, "examples", "train_transformer_lm.py"),
         "--mesh", "1,1,1,1,1", "--steps", "6", "--d-model", "32",
         "--n-layers", "2", "--d-ff", "64", "--seq-len", "64",
         "--generate", "8"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "generated 8 tokens" in r.stdout, r.stdout


def test_gqa_decode_matches_full_forward_and_shrinks_cache():
    """Grouped-query attention: cached decode equals the full causal
    forward, and the KV cache holds only n_kv_heads heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params, init_kv_cache,
        transformer_decode_step, transformer_forward_single,
        transformer_generate)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=8,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_len=16)
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=5)
    assert params["layers"]["wk"].shape[-1] == 2 * (32 // 8)

    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 6)), jnp.int32)
    full = transformer_forward_single(params, tokens, cfg)
    cache = init_kv_cache(cfg, 2, max_len=16)
    assert cache["k"].shape == (2, 2, 2, 16, 4)   # (L, b, KV heads, T, hd)
    for t in range(6):
        logits, cache = transformer_decode_step(
            params, cache, tokens[:, t], t, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]), rtol=2e-4,
                                   atol=2e-4)
    gen = transformer_generate(params, tokens[:, :3], steps=2, cfg=cfg)
    assert gen.shape == (2, 2)


def test_gqa_train_step_tp_sharded():
    """GQA trains under tensor parallelism when tp divides n_kv_heads;
    an indivisible layout raises a clear error."""
    import jax
    import numpy as np
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params,
        make_transformer_train_step)

    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=8,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_len=32)
    mesh = make_mesh((2, 1, 2, 1, 1),
                     axis_names=("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=0)
    step = make_transformer_train_step(cfg, mesh, lr=0.05)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 32, (4, 16)).astype(np.int32)
    tgt = rng.randint(0, 32, (4, 16)).astype(np.int32)
    params, l1 = step(params, tok, tgt)
    params, l2 = step(params, tok, tgt)
    assert float(l2) < float(l1)

    import pytest as _pytest
    bad = TransformerConfig(vocab_size=32, d_model=32, n_heads=8,
                            n_kv_heads=1, n_layers=2, d_ff=64,
                            max_len=32)
    with _pytest.raises(ValueError, match="n_kv_heads"):
        make_transformer_train_step(bad, mesh, lr=0.05)


def test_rope_decode_matches_full_forward():
    """RoPE positions (pos_type='rope'): cached decode (rotated keys in
    the cache) equals the full causal forward; the sp-sharded train
    step agrees with the single-device forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params, init_kv_cache,
        transformer_decode_step, transformer_forward_single,
        transformer_generate, make_transformer_train_step)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_len=16, pos_type="rope")
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=2)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
    full = transformer_forward_single(params, tokens, cfg)
    cache = init_kv_cache(cfg, 2, max_len=16)
    for t in range(8):
        logits, cache = transformer_decode_step(
            params, cache, tokens[:, t], t, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]), rtol=3e-4,
                                   atol=3e-4)
    gen = transformer_generate(params, tokens[:, :4], steps=3, cfg=cfg)
    assert gen.shape == (2, 3)

    # sp=2 sharded train loss must match the replicated forward's loss
    mesh2 = make_mesh((1, 2, 1, 1, 1),
                      axis_names=("dp", "sp", "tp", "pp", "ep"))
    params2, _ = init_transformer_params(cfg, mesh2, seed=2)
    step = make_transformer_train_step(cfg, mesh2, lr=0.0)
    tgt = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
    _, loss = step(params2, tokens, tgt)
    logp = jax.nn.log_softmax(full, axis=-1)
    want = -np.take_along_axis(np.asarray(logp),
                               np.asarray(tgt)[..., None], -1).mean()
    np.testing.assert_allclose(float(loss), want, rtol=2e-3)


def test_generate_sampling_modes():
    """temperature/top_k decode rules: greedy default unchanged;
    sampling is deterministic per seed, varies across seeds, and top-k
    restricts to high-probability tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, init_transformer_params, transformer_generate)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_len=24)
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=7)
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, 64, (2, 6)), jnp.int32)

    g1 = transformer_generate(params, prompt, 6, cfg)
    g2 = transformer_generate(params, prompt, 6, cfg)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    s1 = transformer_generate(params, prompt, 6, cfg, temperature=1.0,
                              seed=1)
    s2 = transformer_generate(params, prompt, 6, cfg, temperature=1.0,
                              seed=1)
    s3 = transformer_generate(params, prompt, 6, cfg, temperature=1.0,
                              seed=9)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert not np.array_equal(np.asarray(s1), np.asarray(s3))

    t1 = transformer_generate(params, prompt, 6, cfg, temperature=1.0,
                              top_k=1, seed=4)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(g1))


# ---------------------------------------------------------------------------
# device scan loop (engine-bulking analog): k steps in one program must
# reproduce k sequential single-step dispatches exactly
# ---------------------------------------------------------------------------

def test_transformer_device_loop_matches_stepwise():
    cfg = _DENSE
    mesh = make_mesh((2, 1, 2, 1, 1),
                     axis_names=("dp", "sp", "tp", "pp", "ep"))
    params, _ = init_transformer_params(cfg, mesh, seed=0)
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (3, 4, 32)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, cfg.vocab_size, (3, 4, 32)), jnp.int32)
    loop = make_transformer_train_step(cfg, mesh, lr=0.1, device_loop=True)
    p_loop, last_loss = loop(params, toks, tgts)

    step = make_transformer_train_step(cfg, mesh, lr=0.1)
    p_seq, _ = init_transformer_params(cfg, mesh, seed=0)
    for i in range(3):
        p_seq, loss = step(p_seq, toks[i], tgts[i])
    assert abs(float(last_loss) - float(loss)) < 1e-5
    ref = {jax.tree_util.keystr(k): v for k, v in
           jax.tree_util.tree_leaves_with_path(p_seq)}
    for k, v in jax.tree_util.tree_leaves_with_path(p_loop):
        ks = jax.tree_util.keystr(k)
        np.testing.assert_allclose(np.asarray(v), np.asarray(ref[ks]),
                                   rtol=1e-4, atol=1e-5, err_msg=ks)


def test_sharded_trainer_run_steps_matches_stepwise():
    from mxnet_tpu.models import mlp
    from mxnet_tpu.parallel import ShardedTrainer
    net = mlp()
    mesh = make_mesh((2,), axis_names=("dp",))
    k, batch = 3, 8
    trainer = ShardedTrainer(net, mesh, lr=0.1, momentum=0.9, dp_axis="dp")
    params, moms, aux = trainer.init((batch, 784), (batch,))
    # run_steps donates its inputs; keep pristine copies for the
    # sequential replay
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)  # noqa: E731
    params2, moms2, aux2 = copy(params), copy(moms), copy(aux)
    rng = np.random.RandomState(0)
    data = rng.randn(k, batch, 784).astype(np.float32)
    label = rng.randint(0, 10, (k, batch)).astype(np.float32)
    key = jax.random.PRNGKey(7)
    d, l = trainer.stage_many(data, label)
    p1, m1, a1, loss1 = trainer.run_steps(params, moms, aux, d, l, key=key)

    for i in range(k):
        params2, moms2, aux2, loss2 = trainer.step(
            params2, moms2, aux2, data[i], label[i],
            key=jax.random.fold_in(key, i))
    assert abs(float(loss1) - float(loss2)) < 1e-6
    for name in p1:
        np.testing.assert_allclose(np.asarray(p1[name]),
                                   np.asarray(params2[name]),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(m1[name]),
                                   np.asarray(moms2[name]),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
