"""The serving driver's own arithmetic: the tree its reference declares
is the one the program's ``init_transformer_params`` builds, and the
schedule is one set of gaps and lengths for every seed. CPU only."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402

CELL = harness.Cell(ROOT, "cerebras_gpt_1p3b.decode_chat", rehearse=True)
DRIVER = harness.load_module(CELL.driver_file)


def test_reference_tree_is_the_programs_layout():
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import init_transformer_params
    cfg = DRIVER.model_config(CELL.config)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    theirs, _specs = init_transformer_params(cfg, mesh, seed=0)
    make = lambda seed: DRIVER.make_params(
        CELL.reference(), CELL.config, seed, jax.devices()[0])
    ours = make(3000000019)
    sig = lambda t: jax.tree_util.tree_map(
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    assert sig(ours) == sig(theirs)
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    # the same distribution: normal(0, 0.02) maps, unit gains, zero shifts
    assert abs(float(np.std(np.asarray(ours["layers"]["w1"]))) - 0.02) < 2e-3
    assert abs(float(np.std(np.asarray(theirs["layers"]["w1"]))) - 0.02) \
        < 2e-3
    assert float(np.min(np.asarray(ours["lnf_g"]))) == 1.0
    assert float(np.max(np.abs(np.asarray(ours["layers"]["ln1_b"])))) == 0.0
    # the same seed gives the same weights, another seed others
    assert np.array_equal(np.asarray(ours["embed"]),
                          np.asarray(make(3000000019)["embed"]))
    assert not np.array_equal(np.asarray(ours["embed"]),
                              np.asarray(make(7)["embed"]))


def test_full_size_shapes_add_up_to_the_published_model():
    real = harness.Cell(ROOT, "cerebras_gpt_1p3b.decode_chat")
    m = real.config["model"]
    leaves = []
    for v in real.reference().param_tree(m).values():
        leaves += list(v.values()) if isinstance(v, dict) else [v]
    n = sum(int(np.prod(shape)) for shape, _kind in leaves)
    assert n == 1315280896              # 1.3 B, without GPT-2's biases
    pub = real.config["published"]
    assert (m["d_model"], m["n_layers"], m["n_heads"], m["d_ff"],
            m["vocab_size"], m["max_len"]) == (
        pub["n_embd"], pub["n_layer"], pub["n_head"], pub["n_inner"],
        pub["vocab_size"], pub["n_positions"])


def test_every_seed_gets_the_same_gaps_and_lengths_in_another_order():
    tr = harness.Cell(ROOT, "cerebras_gpt_1p3b.decode_chat").traffic
    ramp, seconds = tr["ramp_seconds"], 20.0
    a = DRIVER.schedule(tr, seconds, 3000000019, 50257)
    b = DRIVER.schedule(tr, seconds, 11, 50257)
    n_ramp = round(tr["rate_per_s"] * ramp)
    n_win = round(tr["rate_per_s"] * seconds)
    assert len(a) == len(b) == n_ramp + n_win
    shape = lambda plan: [(round(d, 9), len(p), o) for d, p, o in plan]
    # the ramp is the same requests at the same times for every seed
    assert shape(a)[:n_ramp] == shape(b)[:n_ramp]
    assert all(d < ramp for d, _p, _o in a[:n_ramp])
    assert all(ramp <= d < ramp + seconds for d, _p, _o in a[n_ramp:])
    # the window holds the same requests and gaps, begun at another one
    wa, wb = a[n_ramp:], b[n_ramp:]
    sizes = lambda plan: [(len(p), o) for _d, p, o in plan]
    assert sizes(wa) != sizes(wb) and sorted(sizes(wa)) == sorted(sizes(wb))
    assert any(sizes(wa) == sizes(wb)[k:] + sizes(wb)[:k]
               for k in range(n_win))
    gaps = lambda plan: np.sort(np.diff([ramp] + [d for d, _p, _o in plan]))
    assert np.allclose(gaps(wa), gaps(wb))
    # so every seed's window asks for the same number of output tokens
    assert sum(o for _d, _p, o in wa) == sum(o for _d, _p, o in wb)
    assert a == DRIVER.schedule(tr, seconds, 3000000019, 50257)
    # lengths keep to the mix's clips; prompts are longer than answers
    p = np.array([len(x) for _d, x, _o in a])
    o = np.array([x for _d, _p, x in a])
    assert p.min() >= tr["prompt_tokens"]["min"]
    assert p.max() <= tr["prompt_tokens"]["max"]
    assert o.min() >= tr["output_tokens"]["min"]
    assert o.max() <= tr["output_tokens"]["max"]
    assert np.median(p) > np.median(o)
    assert all(0 <= t < 50257 for _d, x, _o in a[:5] for t in x)
    assert a[0][1] != b[0][1]           # the tokens are the seed's
