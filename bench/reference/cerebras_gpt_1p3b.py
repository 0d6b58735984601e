"""Plain reference for the ``cerebras_gpt_1p3b`` configuration: the GPT-2
forward pass (Radford et al. 2019; Cerebras-GPT, arXiv:2304.03208, keeps
the architecture) over one whole sequence — no cache, no paging, no
batching, no kernels — in straightforward ``jax.numpy``, float32 math under
``jax.default_matmul_precision("highest")``. Independent of ``mxnet_tpu``;
it only takes the parameter tree the engine serves:

    embed (V, d), pos (P, d), lnf_g/lnf_b (d,), layers.* stacked
    (1, L, ...): ln1_g ln1_b wq wk wv wo ln2_g ln2_b w1 w2

    x_0   = embed[tokens] + pos[0..s)
    a     = LN1(x);  q, k, v = a wq, a wk, a wv      (heads of d / n_heads)
    x    += softmax(causal(q k^T / sqrt(hd))) v  wo
    x    += gelu(LN2(x) w1) w2
    logits = LNf(x) embed^T                          (tied embeddings)

Departures of the program's block from the published model, kept here so
that the two compute the same function (the configuration file lists
them): the six linear maps have no bias; GELU is the tanh approximation
(the published config says ``gelu``, the erf form). LayerNorm eps 1e-5 as
published.

The stored weights (bf16 in the serving cell) are cast to float32 one
layer at a time inside the scan, so the reference never holds a second
full copy of the model.

``param_tree`` is the benchmark's ONE statement of that parameter tree:
the shapes this forward reads, and how a fresh model draws each (GPT-2's
normal(0, 0.02) maps, unit gains, zero shifts). The serving driver makes
the served weights from it and knows no model itself, so a configuration
with another block brings another reference and edits no driver.
"""
import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
INIT_STD = 0.02


def param_tree(model):
    """name -> (shape, "normal" | "ones" | "zeros") from the configuration
    file's ``model`` sizes; layer stacks lead with (1, n_layers)."""
    d, f, n = model["d_model"], model["d_ff"], model["n_layers"]
    layers = {"ln1_g": ((1, n, d), "ones"), "ln1_b": ((1, n, d), "zeros"),
              "ln2_g": ((1, n, d), "ones"), "ln2_b": ((1, n, d), "zeros"),
              "wq": ((1, n, d, d), "normal"), "wk": ((1, n, d, d), "normal"),
              "wv": ((1, n, d, d), "normal"), "wo": ((1, n, d, d), "normal"),
              "w1": ((1, n, d, f), "normal"), "w2": ((1, n, f, d), "normal")}
    return {"embed": ((model["vocab_size"], d), "normal"),
            "pos": ((model["max_len"], d), "normal"),
            "lnf_g": ((d,), "ones"), "lnf_b": ((d,), "zeros"),
            "layers": layers}


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


@functools.partial(jax.jit, static_argnames=("n_heads",))
def _forward(params, tokens, n_heads):
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        d = params["embed"].shape[1]
        hd = d // n_heads
        x = params["embed"][tokens].astype(f32) \
            + params["pos"][:s].astype(f32)
        causal = jnp.tril(jnp.ones((s, s), bool))

        def block(x, lp):
            lp = jax.tree_util.tree_map(lambda w: w.astype(f32), lp)
            a = _ln(x, lp["ln1_g"], lp["ln1_b"])
            q = (a @ lp["wq"]).reshape(s, n_heads, hd)
            k = (a @ lp["wk"]).reshape(s, n_heads, hd)
            v = (a @ lp["wv"]).reshape(s, n_heads, hd)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(hd))
            sc = jnp.where(causal[None], sc, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
            x = x + o.reshape(s, d) @ lp["wo"]
            h = _ln(x, lp["ln2_g"], lp["ln2_b"])
            x = x + jax.nn.gelu(h @ lp["w1"], approximate=True) @ lp["w2"]
            return x, None

        layers = jax.tree_util.tree_map(lambda w: w[0], params["layers"])
        x, _ = jax.lax.scan(block, x, layers)
        x = _ln(x, params["lnf_g"].astype(f32), params["lnf_b"].astype(f32))
        return x @ params["embed"].astype(f32).T


def forward(params, tokens, n_heads, pad_to=None):
    """Logits (len(tokens), V), float32. ``pad_to`` pads the sequence (the
    mask is causal, so the padding changes nothing before it) so that
    sequences of many lengths share one compiled program."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    return _forward(params, tokens, n_heads=n_heads)[:n]
