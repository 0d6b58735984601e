"""Plain reference for the ``smallthinker_21b_a3b`` configuration:
SmallThinker-21BA3B-Instruct's forward pass (PowerInfer, arXiv:2507.20984;
``config.json`` of huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct)
over one whole sequence — no cache, no paging, no batching, no kernels, no
sorting of rows — in straightforward ``jax.numpy``, float32 math under
``jax.default_matmul_precision("highest")``. Independent of ``mxnet_tpu``;
it only takes the parameter tree the engine serves:

    embed (V, h), head (h, V), lnf_g (h,), layers.* stacked (1, L, ...):
    ln1_g ln2_g (h)  wq (h, Hq*hd)  wk wv (h, Hkv*hd)  wo (Hq*hd, h)
    gate (h, E)  we_gate we_up (E, h, f)  we_down (E, f, h)

    x_0 = embed[tokens]                               no position table
    for l, kind_l = window if sliding_window_layout[l] else global:
      r = x_l gate                                    the router reads the
                                                      layer's INPUT [assumed]
      a = RMSNorm(x_l; ln1_g);  q, k, v = a wq, a wk, a wv
      window layer: q, k = RoPE(q, k; theta);  mask 0 <= i - j < window
      global layer: no rotation (NoPE);        mask j <= i
      y = x_l + softmax(q k^T / sqrt(hd) + mask) v  wo      (GQA)
      m = RMSNorm(y; ln2_g)
      S = the top_k largest of r;  w = softmax(r[S])
      x_{l+1} = y + sum_{e in S} w_e (relu(m we_gate[e]) * (m we_up[e]))
                                     we_down[e]          no capacity
    logits = RMSNorm(x_L; lnf_g) head                    untied

``assumed`` (the configuration file gives the reasons): the router's input
is the un-normalised residual stream entering the layer; an expert is one
ReGLU MLP with ReLU. RoPE pairs dimension i with i + hd/2 (the
``rotate_half`` convention of the family's published code).

Every expert is applied to every row and masked by the row's choice, one
expert at a time. The stored weights (bf16 in the serving cell) are cast to
float32 one layer — and inside a layer one expert — at a time; attention
is computed in blocks of queries and the output map in blocks of the
vocabulary, so that a 6 k-token sequence fits beside 11 GB of weights.

``param_tree`` is the benchmark's ONE statement of that parameter tree.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 512
V_BLOCKS = 8


def param_tree(model):
    """name -> (shape, kind) from the configuration file's ``model``
    sizes; layer stacks lead with (1, n_layers). ``kind`` is "ones",
    "normal" (deviation ``INIT_STD``) or "unit_scores" (``init_std``)."""
    h, n, f = model["d_model"], model["n_layers"], model["d_ff"]
    e, hd = model["num_experts"], model["head_dim"]
    dq, dkv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    layers = {"ln1_g": ((1, n, h), "ones"), "ln2_g": ((1, n, h), "ones"),
              "wq": ((1, n, h, dq), "unit_scores"),
              "wk": ((1, n, h, dkv), "unit_scores"),
              "wv": ((1, n, h, dkv), "normal"),
              "wo": ((1, n, dq, h), "normal"),
              "gate": ((1, n, h, e), "normal"),
              "we_gate": ((1, n, e, h, f), "normal"),
              "we_up": ((1, n, e, h, f), "normal"),
              "we_down": ((1, n, e, f, h), "normal")}
    return {"embed": ((model["vocab_size"], h), "normal"),
            "head": ((h, model["vocab_size"]), "normal"),
            "lnf_g": ((h,), "ones"), "layers": layers}


def init_std(kind, model):
    """The deviation a random map of ``kind`` is drawn with. The query
    and key maps ("unit_scores") take ``d_model ** -0.5``: a normalised
    input then gives q and k of unit variance and the scores q k^T /
    sqrt(hd) a deviation of about one AT ANY WIDTH, so attention is
    peaked enough for the positions — the rotation, its absence in a
    global layer, the window — to move the logits and the routing
    (0.01976 at the served width of 2560, where 0.02 gave the same; a
    stand-in of width 64 drawn at 0.02 has scores of 0.03, attends
    uniformly, and no comparison with it sees a position rule)."""
    return model["d_model"] ** -0.5 if kind == "unit_scores" else INIT_STD


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(t, theta):
    """t (s, heads, hd): rotate the pair (i, i + hd/2) of position p by
    p * theta^(-2i/hd)."""
    s, _heads, hd = t.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, window, block):
    """q (s, Hq, hd), k v (s, Hkv, hd) -> (s, Hq, hd); causal, and only
    the last ``window`` keys where ``window`` > 0. Blocks of queries."""
    s, hq, hd = q.shape
    groups = hq // k.shape[1]
    kpos = jnp.arange(s)

    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, 0)
        qb = qb.reshape(block, k.shape[1], groups, hd)
        sc = jnp.einsum("qkgd,lkd->kgql", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        d = (q0 + jnp.arange(block))[:, None] - kpos[None, :]
        seen = (d >= 0) & ((d < window) | (window <= 0))
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        o = jnp.einsum("kgql,lkd->qkgd", jax.nn.softmax(sc, -1), v)
        return o.reshape(block, hq, hd)

    out = jax.lax.map(one, jnp.arange(0, s, block))
    return out.reshape(s, hq, hd)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "top_k", "window", "theta", "eps",
    "rows", "weights_as"))
def _forward(params, tokens, windowed, rotary, start, n_heads, n_kv_heads,
             head_dim, top_k, window, theta, eps, rows, weights_as):
    f32 = jnp.float32

    def load(w):
        # the stored weight in float32; ``weights_as`` first rounds it to
        # a narrower type (what serving in that type would compute with)
        return (w if weights_as is None else w.astype(weights_as)) \
            .astype(f32)

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        block = Q_BLOCK if s % Q_BLOCK == 0 else s
        x = load(params["embed"][tokens])

        def layer(x, xs):
            lp, is_window, is_rotary = xs
            small = dict((n, load(lp[n])) for n in (
                "ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "gate"))
            r = x @ small["gate"]                       # the layer's input
            a = _rms(x, small["ln1_g"], eps)
            q = (a @ small["wq"]).reshape(s, n_heads, head_dim)
            k = (a @ small["wk"]).reshape(s, n_kv_heads, head_dim)
            v = (a @ small["wv"]).reshape(s, n_kv_heads, head_dim)
            q = jnp.where(is_rotary, _rope(q, theta), q)
            k = jnp.where(is_rotary, _rope(k, theta), k)
            o = _attention(q, k, v, jnp.where(is_window, window, 0), block)
            y = x + o.reshape(s, n_heads * head_dim) @ small["wo"]
            m = _rms(y, small["ln2_g"], eps)
            top, chosen = jax.lax.top_k(r, top_k)        # (s, k)
            w = jax.nn.softmax(top, -1)
            n_experts = r.shape[1]
            # weight of every expert for every row: 0 where not chosen
            share = jnp.sum(
                w[:, :, None] * (chosen[:, :, None]
                                 == jnp.arange(n_experts)[None, None, :]),
                axis=1)                                  # (s, E)

            def expert(acc, e):
                wg = load(lp["we_gate"][e])
                wu = load(lp["we_up"][e])
                wd = load(lp["we_down"][e])
                out = (jax.nn.relu(m @ wg) * (m @ wu)) @ wd
                return acc + share[:, e][:, None] * out, None

            moe, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                                  jnp.arange(n_experts))
            return y + moe, chosen

        stack = jax.tree_util.tree_map(lambda w: w[0], params["layers"])
        x, experts = jax.lax.scan(layer, x, (stack, windowed, rotary))
        x = _rms(x, load(params["lnf_g"]), eps)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
        head = params["head"]
        cuts = np.linspace(0, head.shape[1], V_BLOCKS + 1).astype(int)
        logits = jnp.concatenate(
            [x @ load(head[:, a:b])
             for a, b in zip(cuts[:-1], cuts[1:])], axis=1)
        return logits, experts


def forward(params, tokens, model, pad_to=None, logits_from=0,
            logits_rows=None, weights_as=None):
    """``(logits, experts)``: float32 logits of positions ``logits_from
    ... logits_from + logits_rows`` (default: to the sequence's end) and
    each layer's chosen experts (L, len(tokens), top_k), the largest
    router logit first. ``pad_to`` pads the sequence (the mask is causal,
    so the padding changes nothing before it) so that sequences of many
    lengths share one compiled program; so does a fixed ``logits_rows``
    (rows past the sequence's end are the padding's). ``weights_as`` (a
    dtype name) rounds every stored weight to that type before use: the
    reading of a precision below the served one, for setting a limit.
    A ``model`` with another ``rope_layout`` or ``window_layout`` is
    another model: what a program that broke that rule would compute."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    rows = int(logits_rows) if logits_rows else n - int(logits_from)
    # a slice that would pass the (padded) end starts earlier instead,
    # and the rows before ``logits_from`` are dropped again below
    start = min(int(logits_from), tokens.shape[0] - rows)
    layers = int(model["n_layers"])
    logits, experts = _forward(
        params, tokens,
        jnp.asarray(model["window_layout"][:layers], bool),
        jnp.asarray(model["rope_layout"][:layers], bool),
        jnp.asarray(start, jnp.int32),
        n_heads=int(model["n_heads"]), n_kv_heads=int(model["n_kv_heads"]),
        head_dim=int(model["head_dim"]), top_k=int(model["moe_top_k"]),
        window=int(model["sliding_window"]),
        theta=float(model["rope_base"]), eps=float(model["norm_eps"]),
        rows=rows, weights_as=weights_as)
    return logits[int(logits_from) - start:], experts[:, :n]
