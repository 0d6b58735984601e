"""Plain reference for the ``deepseek_v3`` configuration: DeepSeek-V3's
forward pass (arXiv:2412.19437 sec. 2.1.1 multi-head latent attention,
sec. 2.1.2 DeepSeekMoE with auxiliary-loss-free routing; the attention
itself arXiv:2405.04434 sec. 2.1; ``config.json`` of
huggingface.co/deepseek-ai/DeepSeek-V3) over one whole sequence — no cache,
no paging, no batching, no kernels, no absorption of the up-projections, no
sorting of rows — in straightforward ``jax.numpy``, float32 math under
``jax.default_matmul_precision("highest")``. Independent of ``mxnet_tpu``;
it only takes the parameter tree the engine serves:

    embed (V, h), head (h, V), lnf_g (h,); two stacks of layers, each leaf
    (1, layers, ...): ``dense_layers`` (the leading dense layers) and
    ``layers`` (the expert layers). Both hold
      ln1_g ln2_g (h)   wq_a (h, rq)   q_ln_g (rq)   wq_b (rq, H (dn + dr))
      wkv_a (h, r + dr)   kv_ln_g (r)   wkv_b (r, H (dn + dv))
      wo (H dv, h)
    a dense layer:   w_gate w_up (h, F)   w_down (F, h)
    an expert layer: gate (h, E)   gate_bias (E)   we_gate we_up (E_here,
      h, f)   we_down (E_here, f, h)   ws_gate ws_up (h, fs)   ws_down

    x_0 = embed[tokens]
    for every layer, a = RMSNorm(x; ln1_g):
      c_Q = RMSNorm(a wq_a; q_ln_g)
      [q_nope_i ; q_rope_i] = c_Q wq_b          head i of H;  q_rope rotated
      [c_KV ; k_r] = a wkv_a;  c_KV = RMSNorm(c_KV; kv_ln_g);  k_r rotated,
                                                ONE for all heads
      [k_nope_i ; v_i] = c_KV wkv_b
      score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_rope_i(t) . k_r(s))
                      * (dn + dr)^-0.5 * m^2    s <= t;  m = 0.1 mscale_all_dim
                                                ln(factor) + 1  (YaRN)
      y = x + concat_i( softmax(score_i) v_i ) wo
      m = RMSNorm(y; ln2_g);   E(z; g, u, d) = (silu(z g) * (z u)) d
      dense layer:  x' = y + E(m; w_gate, w_up, w_down)
      expert layer: s = sigmoid(m gate)                        float32
                    s' = s + gate_bias          the CHOICE only
                    G groups of E / G consecutive experts; a group scores
                    the sum of its two largest s'; the topk_groups best
                    stay; S = the top_k largest s' among their experts
                    w_e = s_e / (sum_{S} s + 1e-20) * routed_scale
                    x' = y + sum_{e in S, e held here} w_e E(m; we_*[e])
                           + E(m; ws_gate, ws_up, ws_down)
    logits = RMSNorm(x_L; lnf_g) head                          untied

Rotation: over the ``dr`` rope dimensions, pair (i, i + dr/2) [the
``rotate_half`` convention; the published code first permutes interleaved
pairs into halves, which with drawn weights is a relabelling of wq_b's and
wkv_a's columns: ``assumed``], frequencies theta^(-2i/dr) blended with their
``factor``-fold interpolation by YaRN's ramp (``beta_fast``, ``beta_slow``,
``original_max_position_embeddings``); cos and sin carry the factor
mscale / mscale_all_dim's ratio (1 as published).

What this chip does not hold is left out HERE as in the program (the
model-configs guide, section 4): the experts outside ``moe_local_experts``
add nothing, and that partial sum goes on to the next layer; the vocabulary
is the configuration's slice. The multi-token-prediction module is not
built (``departures`` in the configuration file).

Every held expert is applied to every row and masked by the row's choice,
one expert at a time. The stored weights (bf16 in the serving cell) are
cast to float32 a map — inside the dense FFN a block of columns, inside
the attention a group of heads — at a time; attention is computed in
blocks of queries, the output map in blocks of the vocabulary, so that a
6 k-token sequence fits beside 9 GB of weights.

``param_tree`` is the benchmark's ONE statement of that parameter tree.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 512
HEAD_GROUP = 16
F_BLOCK = 2048
V_BLOCKS = 8


def _attention_tree(model, n):
    h, nh = model["d_model"], model["n_heads"]
    rq, r = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return {"ln1_g": ((1, n, h), "ones"), "ln2_g": ((1, n, h), "ones"),
            "wq_a": ((1, n, h, rq), "normal"),
            "q_ln_g": ((1, n, rq), "ones"),
            "wq_b": ((1, n, rq, nh * (dn + dr)), "unit_from_q_rank"),
            "wkv_a": ((1, n, h, r + dr), "unit_from_width"),
            "kv_ln_g": ((1, n, r), "ones"),
            "wkv_b": ((1, n, r, nh * (dn + dv)), "unit_from_kv_rank"),
            "wo": ((1, n, nh * dv, h), "normal")}


def param_tree(model):
    """name -> (shape, kind) from the configuration file's ``model``
    sizes; layer stacks lead with (1, layers). ``kind`` is "ones",
    "normal" (deviation ``INIT_STD``) or one of the "unit_from_*"
    (``init_std``)."""
    h, f = model["d_model"], model["d_ff"]
    nd = model["dense_layers"]
    nm = model["n_layers"] - nd
    e, held = model["num_experts"], model["moe_local_experts"][1]
    fs, fd = model["moe_shared_width"], model["d_ff_dense"]
    dense = dict(_attention_tree(model, nd),
                 w_gate=((1, nd, h, fd), "normal"),
                 w_up=((1, nd, h, fd), "normal"),
                 w_down=((1, nd, fd, h), "normal"))
    layers = dict(_attention_tree(model, nm),
                  gate=((1, nm, h, e), "normal"),
                  gate_bias=((1, nm, e), "normal"),
                  we_gate=((1, nm, held, h, f), "normal"),
                  we_up=((1, nm, held, h, f), "normal"),
                  we_down=((1, nm, held, f, h), "normal"),
                  ws_gate=((1, nm, h, fs), "normal"),
                  ws_up=((1, nm, h, fs), "normal"),
                  ws_down=((1, nm, fs, h), "normal"))
    return {"embed": ((model["vocab_size"], h), "normal"),
            "head": ((h, model["vocab_size"]), "normal"),
            "lnf_g": ((h,), "ones"), "dense_layers": dense,
            "layers": layers}


def init_std(kind, model):
    """The deviation a random map of ``kind`` is drawn with. The maps
    that make queries and keys from a NORMALISED input take its width to
    the power -1/2 — ``wq_b`` (from c_Q, q_lora_rank wide), ``wkv_b``
    (from c_KV, kv_lora_rank wide), ``wkv_a`` (from the layer's input; its
    c_KV columns are normalised again, its k_r columns are a key) — so
    that q and k have unit variance and the scores a deviation of about
    m^2 = 1.87 AT ANY WIDTH: attention is peaked enough for the
    positions, the rotation and the scale to move the logits and the
    routing (a stand-in of width 64 drawn at 0.02 attends uniformly, and
    no comparison with it sees a position rule). Everything else,
    the router's bias included, 0.02: the bias then moves the choice
    wherever two scores s' lie within a few hundredths, as the eighth and
    ninth of 256 mostly do."""
    width = {"unit_from_q_rank": model["q_lora_rank"],
             "unit_from_kv_rank": model["kv_lora_rank"],
             "unit_from_width": model["d_model"]}.get(kind)
    return width ** -0.5 if width else INIT_STD


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _yarn(dim, theta, rs):
    """``(inv_freq (dim / 2,), factor of cos and sin, m)`` of the rotation
    over ``dim`` dimensions under ``rope_scaling`` ``rs`` (None: plain)."""
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / dim)
    if not rs:
        return freq.astype(np.float32), 1.0, 1.0

    def correction_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv_freq = freq / rs["factor"] * ramp + freq * (1 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(rs["factor"]) + 1.0 \
            if rs["factor"] > 1 else 1.0

    m = mscale(rs.get("mscale_all_dim", 0))
    return inv_freq.astype(np.float32), mscale(rs.get("mscale", 1)) / m, m


def _rope(t, inv_freq, factor):
    """t (s, heads, hd): rotate the pair (i, i + hd/2) of position p by
    p * inv_freq[i]."""
    half = t.shape[-1] // 2
    ang = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(c_q, c_kv, k_r, wq_b, wkv_b, load, dn, dr, dv, scale,
               inv_freq, factor):
    """Decompressed latent attention, causal: c_q (s, rq), c_kv (s, r),
    k_r (s, dr) rotated -> (s, H dv). A group of heads at a time, blocks
    of queries inside."""
    s = c_q.shape[0]
    n_heads = wq_b.shape[1] // (dn + dr)
    hg = HEAD_GROUP if n_heads % HEAD_GROUP == 0 else n_heads
    groups = n_heads // hg
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    wq_b = wq_b.reshape(-1, groups, hg * (dn + dr))
    wkv_b = wkv_b.reshape(-1, groups, hg * (dn + dv))
    kpos = jnp.arange(s)

    def group(g):
        q = (c_q @ load(wq_b[:, g])).reshape(s, hg, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv_freq, factor)
        kv = (c_kv @ load(wkv_b[:, g])).reshape(s, hg, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def one(q0):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, block, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, block, 0)
            sc = (jnp.einsum("qhd,lhd->hql", qn, k_nope)
                  + jnp.einsum("qhd,ld->hql", qr, k_r)) * scale
            seen = (q0 + jnp.arange(block))[:, None] >= kpos[None, :]
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("hql,lhd->qhd", jax.nn.softmax(sc, -1), v)

        return jax.lax.map(one, jnp.arange(0, s, block)).reshape(
            s, hg, dv)

    out = jax.lax.map(group, jnp.arange(groups))        # (G, s, hg, dv)
    return out.transpose(1, 0, 2, 3).reshape(s, n_heads * dv)


def _swiglu(z, gate, up, down, load, block=None):
    """``(silu(z gate) * (z up)) down``; a wide one in blocks of
    columns."""
    width = gate.shape[1]
    block = width if not block or width % block else block
    out = jnp.zeros(z.shape[:1] + (down.shape[1],), jnp.float32)
    for a in range(0, width, block):
        out = out + (jax.nn.silu(z @ load(gate[:, a:a + block]))
                     * (z @ load(up[:, a:a + block]))) \
            @ load(down[a:a + block])
    return out


def _route(scores, bias, top_k, n_groups, topk_groups, routed_scale):
    """scores (s, E) = sigmoid(router logits): ``(chosen (s, k), weight of
    every expert for every row (s, E), 0 where not chosen)``."""
    n, n_exp = scores.shape
    choice = scores + bias
    if n_groups > 1:
        per = n_exp // n_groups
        two = jnp.sort(choice.reshape(n, n_groups, per), -1)[..., -2:]
        _best, kept = jax.lax.top_k(two.sum(-1), topk_groups)
        group_of = jnp.arange(n_exp) // per                     # (E,)
        in_kept = jnp.any(group_of[None, :, None] == kept[:, None, :], -1)
        choice = jnp.where(in_kept, choice, -jnp.inf)
    _top, chosen = jax.lax.top_k(choice, top_k)                 # (s, k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * routed_scale
    share = jnp.sum(w[:, :, None] * (chosen[:, :, None]
                                     == jnp.arange(n_exp)[None, None, :]),
                    axis=1)
    return chosen, share


@functools.partial(jax.jit, static_argnames=("sizes", "rows", "weights_as"))
def _forward(params, tokens, start, sizes, rows, weights_as):
    (dn, dr, dv, r, eps, theta, rope_scaling, top_k, n_groups, topk_groups,
     routed_scale, first) = sizes
    f32 = jnp.float32
    inv_freq, factor, m = _yarn(dr, theta, dict(rope_scaling)
                                if rope_scaling else None)
    scale = (dn + dr) ** -0.5 * m * m

    def load(w):
        # the stored weight in float32; ``weights_as`` first rounds it to
        # a narrower type (what serving in that type would compute with)
        return (w if weights_as is None else w.astype(weights_as)) \
            .astype(f32)

    with jax.default_matmul_precision("highest"):
        x = load(params["embed"][tokens])
        s = x.shape[0]
        experts = []
        for name in ("dense_layers", "layers"):
            stack = params[name]
            for l in range(stack["ln1_g"].shape[1]):
                lp = jax.tree_util.tree_map(lambda w: w[0, l], stack)
                a = _rms(x, load(lp["ln1_g"]), eps)
                c_q = _rms(a @ load(lp["wq_a"]), load(lp["q_ln_g"]), eps)
                down = a @ load(lp["wkv_a"])
                c_kv = _rms(down[:, :r], load(lp["kv_ln_g"]), eps)
                k_r = _rope(down[:, None, r:], inv_freq, factor)[:, 0]
                o = _attention(c_q, c_kv, k_r, lp["wq_b"], lp["wkv_b"],
                               load, dn, dr, dv, scale, inv_freq, factor)
                y = x + o @ load(lp["wo"])
                z = _rms(y, load(lp["ln2_g"]), eps)
                if name == "dense_layers":
                    x = y + _swiglu(z, lp["w_gate"], lp["w_up"],
                                    lp["w_down"], load, F_BLOCK)
                    continue
                chosen, share = _route(
                    jax.nn.sigmoid(z @ load(lp["gate"])),
                    load(lp["gate_bias"]), top_k, n_groups, topk_groups,
                    routed_scale)
                experts.append(chosen)

                def expert(acc, j, lp=lp, z=z, share=share):
                    out = _swiglu(z, lp["we_gate"][j], lp["we_up"][j],
                                  lp["we_down"][j], load)
                    w = jax.lax.dynamic_index_in_dim(
                        share, first + j, 1, keepdims=True)
                    return acc + w * out, None

                moe, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                                      jnp.arange(lp["we_gate"].shape[0]))
                x = y + moe + _swiglu(z, lp["ws_gate"], lp["ws_up"],
                                      lp["ws_down"], load)
        x = _rms(x, load(params["lnf_g"]), eps)
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
    each EXPERT layer's chosen experts (expert layers, len(tokens),
    top_k), ids among all ``num_experts``, the largest s' first. ``pad_to``
    pads the sequence (the mask is causal, so the padding changes nothing
    before it) so that sequences of many lengths share one compiled
    program; so does a fixed ``logits_rows`` (rows past the sequence's end
    are the padding's). ``weights_as`` (a dtype name) rounds every stored
    weight to that type before use: the reading of a precision below the
    served one, for setting a limit. A ``model`` with other
    ``moe_n_groups`` / ``moe_topk_groups`` or another ``rope_scaling`` is
    another model: what a program that broke that rule would compute."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    rows = int(logits_rows) if logits_rows else n - int(logits_from)
    # a slice that would pass the (padded) end starts earlier instead,
    # and the rows before ``logits_from`` are dropped again below
    start = min(int(logits_from), tokens.shape[0] - rows)
    rs = model.get("rope_scaling")
    sizes = (int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"]),
             int(model["v_head_dim"]), int(model["kv_lora_rank"]),
             float(model["norm_eps"]), float(model["rope_base"]),
             tuple(sorted(rs.items())) if rs else None,
             int(model["moe_top_k"]), int(model["moe_n_groups"]),
             int(model["moe_topk_groups"]),
             float(model["moe_routed_scale"]),
             int(model["moe_local_experts"][0]))
    logits, experts = _forward(params, tokens, jnp.asarray(start, jnp.int32),
                               sizes=sizes, rows=rows, weights_as=weights_as)
    return logits[int(logits_from) - start:], experts[:, :n]
