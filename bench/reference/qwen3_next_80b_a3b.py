"""Plain reference for the ``qwen3_next_80b_a3b`` configuration:
Qwen3-Next-80B-A3B's forward pass (``config.json`` of huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct; the layer equations as huggingface/transformers
``models/qwen3_next/modeling_qwen3_next.py`` states them; the linear-
attention rule is Gated DeltaNet, arXiv:2412.06464, the delta rule
arXiv:2406.06484) over one whole sequence — no cache, no paging, no
batching, no kernels, no chunked form of the recurrence, no sorting of rows
— in straightforward ``jax.numpy``, float32 math under
``jax.default_matmul_precision("highest")``. Independent of ``mxnet_tpu``;
it only takes the parameter tree the engine serves:

    embed (V, h), head (h, V), lnf_g (h,); two stacks of layers, each leaf
    (1, layers, ...): ``linear_layers`` (the Gated DeltaNet layers) and
    ``layers`` (the full-attention layers); layer l of the model is linear
    where ``linear_layout[l]``, and each stack holds its layers in order.
    Both hold  ln1_g ln2_g (h)  and the expert layer:
      gate (h, E)   we_gate we_up (E_here, h, f)   we_down (E_here, f, h)
      ws_gate ws_up (h, fs)   ws_down (fs, h)   ws_sigmoid (h, 1)
    a full layer:    wq (h, H 2 hd)  wk wv (h, Hkv hd)  wo (H hd, h)
                     q_norm_g k_norm_g (hd)
    a linear layer:  gdn_qkvz (h, 2 Hk dk + 2 Hv dv)   gdn_ba (h, 2 Hv)
                     gdn_conv (4, 2 Hk dk + Hv dv)   gdn_a_log gdn_dt_bias
                     (Hv)   gdn_norm_g (dv)   gdn_out (Hv dv, h)

    N(x; w) = x / sqrt(mean(x^2) + eps) (1 + w)          zero-centred gain
    x_0 = embed[tokens]
    for every layer, a = N(x; ln1_g):
      full layer:   [q_i ; gate_i] = (a wq) head i of H;  k_j, v_j of Hkv
        q_i = N(q_i; q_norm_g), k_j = N(k_j; k_norm_g)    over hd, a head
        the first rotary_share hd dims of q and k rotate, pairs (i, i +
          rot/2), frequencies rope_base^(-2i/rot); the rest pass
        score_i(t, s) = q_i(t) . k_{i // (H/Hkv)}(s) hd^-0.5       s <= t
        y = x + (concat_i softmax(score_i) v * sigmoid(gate)) wo
      linear layer: [q | k | v | z] = a gdn_qkvz;  [b | a'] = a gdn_ba
        c = SiLU(causal depthwise conv, 4 taps, of [q | k | v])
            c_t = sum_j gdn_conv[j] [q|k|v]_{t - 3 + j}   (zeros before 0)
        q_i, k_i of Hk heads: L2-normalised (eps 1e-6), q times dk^-0.5;
        value head j reads key head j // (Hv/Hk)
        beta = sigmoid(b);  g = -exp(gdn_a_log) softplus(a' + gdn_dt_bias)
        per value head, S (dk, dv) = 0 at the start, for each token t:
          S <- S exp(g_t);  d = (v_t - S^T k_t) beta_t;  S <- S + k_t d^T
          o_t = S^T q_t
        y = x + (concat_j RMSNorm(o_j) gdn_norm_g SiLU(z_j)) gdn_out
            (this one norm has a PLAIN gain, not 1 + w)
      m = N(y; ln2_g);   E(u; g, u', d) = (SiLU(u g) * (u u')) d
      p = softmax(m gate) in float32 over all E experts; the top_k
        largest, their weights over their sum (norm_topk_prob)
      x' = y + sum_{chosen e held here} p_e E_e(m)
             + sigmoid(m ws_sigmoid) E(m; ws_gate, ws_up, ws_down)
    logits = N(x_L; lnf_g) head

The fused projections' columns are laid out flat, [q | k | v | z] and [b |
a] — the published checkpoint interleaves them by key head, which with
drawn weights is a relabelling of columns. Departures (the configuration
file lists them): the multi-token-prediction module is not built. With
``moe_local_experts = [first, count]`` only the held experts' terms are
summed: what the absent experts would add is left out, here and in the
program alike (the model-configs guide, section 4).

Attention is computed in blocks of queries and the output map in blocks of
the vocabulary, so that a 6 k-token sequence fits beside the weights.

``param_tree`` is the benchmark's ONE statement of that parameter tree.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 512
V_BLOCKS = 8


def _counts(model):
    layout = model["linear_layout"][:model["n_layers"]]
    n_lin = sum(1 for flag in layout if flag)
    return n_lin, model["n_layers"] - n_lin


def _ffn_tree(model, n):
    h, f, e = model["d_model"], model["d_ff"], model["num_experts"]
    held, fs = model["moe_local_experts"][1], model["moe_shared_width"]
    return {"ln1_g": ((1, n, h), "gain"), "ln2_g": ((1, n, h), "gain"),
            "gate": ((1, n, h, e), "normal"),
            "we_gate": ((1, n, held, h, f), "normal"),
            "we_up": ((1, n, held, h, f), "normal"),
            "we_down": ((1, n, held, f, h), "normal"),
            "ws_gate": ((1, n, h, fs), "normal"),
            "ws_up": ((1, n, h, fs), "normal"),
            "ws_down": ((1, n, fs, h), "normal"),
            "ws_sigmoid": ((1, n, h, 1), "normal")}


def param_tree(model):
    """name -> (shape, kind) from the configuration file's ``model``
    sizes; layer stacks lead with (1, layers). ``kind`` is "ones" or a
    name :func:`init_std` gives the deviation of."""
    h = model["d_model"]
    nh, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    hk, hv = model["linear_key_heads"], model["linear_value_heads"]
    dk, dv = model["linear_key_dim"], model["linear_value_dim"]
    n_lin, n_full = _counts(model)
    full = dict(_ffn_tree(model, n_full),
                wq=((1, n_full, h, nh * 2 * hd), "normal"),
                wk=((1, n_full, h, kvh * hd), "normal"),
                wv=((1, n_full, h, kvh * hd), "normal"),
                wo=((1, n_full, nh * hd, h), "normal"),
                q_norm_g=((1, n_full, hd), "gain"),
                k_norm_g=((1, n_full, hd), "gain"))
    linear = dict(
        _ffn_tree(model, n_lin),
        gdn_qkvz=((1, n_lin, h, 2 * hk * dk + 2 * hv * dv), "normal"),
        gdn_ba=((1, n_lin, h, 2 * hv), "normal"),
        gdn_conv=((1, n_lin, model["linear_conv_width"],
                   2 * hk * dk + hv * dv), "conv"),
        gdn_a_log=((1, n_lin, hv), "decay"),
        gdn_dt_bias=((1, n_lin, hv), "ones"),
        gdn_norm_g=((1, n_lin, dv), "ones"),
        gdn_out=((1, n_lin, hv * dv, h), "normal"))
    return {"embed": ((model["vocab_size"], h), "normal"),
            "head": ((h, model["vocab_size"]), "normal"),
            "lnf_g": ((h,), "gain"), "linear_layers": linear,
            "layers": full}


def init_std(kind, model):
    """The deviation a random parameter of ``kind`` is drawn with (mean
    0). "gain": the zero-centred norms' w, 0.1, so that (1 + w) differs
    from 1 by what bf16 still tells apart; "conv": the four taps, 0.5 (a
    width-4 convolution's default initialisation spans +-0.5); "decay":
    ``A_log``, 2.0 — exp(A_log) then runs from about 0.02 to 50, and with
    softplus(a + 1) about 1.3 the 32 heads' states forget over anything
    from a single token to a few hundred: a state that forgot in three
    tokens everywhere would hide a stale or mis-handed state row from the
    comparison. Everything else 0.02; q and k of the full layers are
    normalised a head (scores of deviation about 1 at head_dim 256) and
    those of the linear layers L2-normalised, so no map needs a width's
    scale for attention to be peaked."""
    return {"gain": 0.1, "conv": 0.5, "decay": 2.0}.get(kind, INIT_STD)


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rotate(t, rot, theta):
    """t (s, heads, hd): the first ``rot`` dims rotate, pair (i, i +
    rot/2) of position p by p theta^(-2i/rot); the rest pass."""
    half = rot // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rot)
    ang = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq.astype(np.float32))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = t[..., :half], t[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., rot:]], -1)


def _attention(q, k, v):
    """Causal softmax attention, q (s, H, hd) against k, v (s, Hkv, hd),
    scale hd^-0.5, a block of queries at a time -> (s, H hd)."""
    s, nh, hd = q.shape
    groups = nh // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    kpos = jnp.arange(s)

    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, 0)
        sc = jnp.einsum("qhd,lhd->hql", qb, k) * hd ** -0.5
        seen = (q0 + jnp.arange(block))[:, None] >= kpos[None, :]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hql,lhd->qhd", jax.nn.softmax(sc, -1), v)

    return jax.lax.map(one, jnp.arange(0, s, block)).reshape(s, nh * hd)


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token: q, k (s, Hv, dk), v (s, Hv,
    dv), g, beta (s, Hv) -> o (s, Hv, dv)."""

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        delta = (v_t - jnp.einsum("hkv,hk->hv", state, k_t)) * b_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _state, o = jax.lax.scan(
        step, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
        (q, k, v, g, beta))
    return o


def _swiglu(z, gate, up, down, load):
    return (jax.nn.silu(z @ load(gate)) * (z @ load(up))) @ load(down)


@functools.partial(jax.jit, static_argnames=("sizes", "rows", "weights_as"))
def _forward(params, tokens, start, sizes, rows, weights_as):
    (layout, nh, kvh, hd, rot, theta, eps, hk, hv, dk, dv, taps, top_k,
     renorm, first) = sizes
    f32 = jnp.float32

    def load(w):
        # the stored weight in float32; ``weights_as`` first rounds it to
        # a narrower type (what serving in that type would compute with)
        return (w if weights_as is None else w.astype(weights_as)) \
            .astype(f32)

    def norm(x, w):
        return _rms(x, eps) * (1.0 + load(w))

    with jax.default_matmul_precision("highest"):
        x = load(params["embed"][tokens])
        s = x.shape[0]
        experts, taken = [], {"linear_layers": 0, "layers": 0}
        for is_linear in layout:
            name = "linear_layers" if is_linear else "layers"
            lp = jax.tree_util.tree_map(
                lambda w, i=taken[name]: w[0, i], params[name])
            taken[name] += 1
            a = norm(x, lp["ln1_g"])
            if is_linear:
                qkvz = a @ load(lp["gdn_qkvz"])
                ba = a @ load(lp["gdn_ba"])
                n_conv = 2 * hk * dk + hv * dv
                xc, z = qkvz[:, :n_conv], qkvz[:, n_conv:]
                xp = jnp.pad(xc, ((taps - 1, 0), (0, 0)))
                w = load(lp["gdn_conv"])
                c = jax.nn.silu(sum(xp[j:j + s] * w[j]
                                    for j in range(taps)))

                def unit(t):
                    return t / jnp.sqrt(
                        jnp.sum(t * t, -1, keepdims=True) + 1e-6)

                q = unit(c[:, :hk * dk].reshape(s, hk, dk)) * dk ** -0.5
                k = unit(c[:, hk * dk:2 * hk * dk].reshape(s, hk, dk))
                q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
                v = c[:, 2 * hk * dk:].reshape(s, hv, dv)
                beta = jax.nn.sigmoid(ba[:, :hv])
                g = -jnp.exp(load(lp["gdn_a_log"])) * jax.nn.softplus(
                    ba[:, hv:] + load(lp["gdn_dt_bias"]))
                o = _delta_rule(q, k, v, g, beta)
                o = _rms(o, eps) * load(lp["gdn_norm_g"]) \
                    * jax.nn.silu(z.reshape(s, hv, dv))
                y = x + o.reshape(s, hv * dv) @ load(lp["gdn_out"])
            else:
                qg = (a @ load(lp["wq"])).reshape(s, nh, 2 * hd)
                q, gate = qg[..., :hd], qg[..., hd:].reshape(s, nh * hd)
                k = (a @ load(lp["wk"])).reshape(s, kvh, hd)
                v = (a @ load(lp["wv"])).reshape(s, kvh, hd)
                q = _rotate(norm(q, lp["q_norm_g"]), rot, theta)
                k = _rotate(norm(k, lp["k_norm_g"]), rot, theta)
                o = _attention(q, k, v) * jax.nn.sigmoid(gate)
                y = x + o @ load(lp["wo"])
            z = norm(y, lp["ln2_g"])
            prob = jax.nn.softmax(z @ load(lp["gate"]), axis=-1)
            top, chosen = jax.lax.top_k(prob, top_k)             # (s, k)
            if renorm:
                top = top / jnp.sum(top, -1, keepdims=True)
            experts.append(chosen)
            share = jnp.sum(top[:, :, None] * (
                chosen[:, :, None]
                == jnp.arange(prob.shape[1])[None, None, :]), axis=1)

            def expert(acc, j, lp=lp, z=z, share=share):
                out = _swiglu(z, lp["we_gate"][j], lp["we_up"][j],
                              lp["we_down"][j], load)
                w = jax.lax.dynamic_index_in_dim(share, first + j, 1,
                                                 keepdims=True)
                return acc + w * out, None

            moe, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                                  jnp.arange(lp["we_gate"].shape[0]))
            shared = _swiglu(z, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                             load) * jax.nn.sigmoid(
                                 z @ load(lp["ws_sigmoid"]))
            x = y + moe + shared
        x = norm(x, params["lnf_g"])
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
        head = params["head"]
        cuts = np.linspace(0, head.shape[1], V_BLOCKS + 1).astype(int)
        logits = jnp.concatenate(
            [x @ load(head[:, a:b])
             for a, b in zip(cuts[:-1], cuts[1:])], axis=1)
        return logits, jnp.stack(experts)


def forward(params, tokens, model, pad_to=None, logits_from=0,
            logits_rows=None, weights_as=None):
    """``(logits, experts)``: float32 logits of positions ``logits_from
    ... logits_from + logits_rows`` (default: to the sequence's end) and
    each layer's chosen experts (layers, len(tokens), top_k), ids among
    all ``num_experts``, the likeliest first. ``pad_to`` pads the sequence
    (attention is causal and the recurrence runs forward, so the padding
    changes nothing before it) so that sequences of many lengths share one
    compiled program; so does a fixed ``logits_rows`` (rows past the
    sequence's end are the padding's). ``weights_as`` (a dtype name)
    rounds every stored weight to that type before use: the reading of a
    precision below the served one, for setting a limit. A ``model`` with
    another ``rotary_share``, or with ``norm_topk_prob`` false (the chosen
    experts' softmax weights left as they are, not renormalised), is
    another model: what a program that broke that rule would compute."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    rows = int(logits_rows) if logits_rows else n - int(logits_from)
    # a slice that would pass the (padded) end starts earlier instead,
    # and the rows before ``logits_from`` are dropped again below
    start = min(int(logits_from), tokens.shape[0] - rows)
    hd = int(model["head_dim"])
    sizes = (tuple(bool(f) for f in
                   model["linear_layout"][:model["n_layers"]]),
             int(model["n_heads"]), int(model["n_kv_heads"]), hd,
             int(round(hd * float(model["rotary_share"]))),
             float(model["rope_base"]), float(model["norm_eps"]),
             int(model["linear_key_heads"]),
             int(model["linear_value_heads"]),
             int(model["linear_key_dim"]), int(model["linear_value_dim"]),
             int(model["linear_conv_width"]), int(model["moe_top_k"]),
             bool(model.get("norm_topk_prob", True)),
             int(model["moe_local_experts"][0]))
    logits, experts = _forward(params, tokens, jnp.asarray(start, jnp.int32),
                               sizes=sizes, rows=rows, weights_as=weights_as)
    return logits[int(logits_from) - start:], experts[:, :n]
