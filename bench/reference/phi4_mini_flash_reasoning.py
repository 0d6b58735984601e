"""Plain reference for the ``phi4_mini_flash_reasoning`` configuration:
Phi-4-mini-flash-reasoning's forward pass (``config.json`` of huggingface.co/
microsoft/Phi-4-mini-flash-reasoning, ``model_type`` phi4flash: SambaY, the
decoder-hybrid-decoder of arXiv:2507.06607 over Samba arXiv:2406.07522,
YOCO arXiv:2405.05254, Mamba arXiv:2312.00752 and the Differential
Transformer arXiv:2410.05258) over one whole sequence — no cache, no
paging, no batching, no kernels, no pairing of heads, no shortcut of the
cross-decoder: EVERY layer runs over EVERY position — in straightforward
``jax.numpy``, float32 math under ``jax.default_matmul_precision
("highest")``. Independent of ``mxnet_tpu``; it only takes the parameter
tree the engine serves:

    embed (V, h), lnf_g lnf_b (h,); four stacks, each leaf (1, layers, ...)
    and its layers in order: ``mamba_layers``, ``layers`` (the self-
    attention layers), ``gmu_layers``, ``cross_layers``. Every layer holds
    ln1_g ln1_b ln2_g ln2_b (h)  w_gate w_up (h, f)  w_down (f, h)
    a Mamba layer:   mb_in (h, 2 di)  mb_conv (4, di)  mb_conv_b (di)
                     mb_x (di, R + 2 N)  mb_dt (R, di)  mb_dt_b (di)
                     mb_a_log (di, N)  mb_d (di)  mb_out (di, h)
    an attention layer: wq (h, H hd) bq  wk wv (h, Hkv hd) bk bv
                     wo (H hd, h) bo  lambda_q1 lambda_k1 lambda_q2
                     lambda_k2 (hd)  subln_g (2 hd)
    a cross layer:   the same without wk bk wv bv
    a GMU layer:     gmu_in (h, di)  gmu_out (di, h)

    LN(x) = (x - mean) / sqrt(var + 1e-5) g + b
    layer l is, before cross_from, Mamba where mamba_layout[l], else
    self-attention (windowed where window_layout[l]); from cross_from on a
    GMU where mamba_layout[l], else cross-attention
    for every layer, a = LN1(x):
      Mamba:  [u | z] = a mb_in;  u = SiLU(causal depthwise conv(u) +
              mb_conv_b) (4 taps, zeros before 0);  [dt | B | C] = u mb_x
              delta = softplus(dt mb_dt + mb_dt_b);  A = -exp(mb_a_log)
              per channel c, s (N,) = 0, for each token t:
                s <- exp(delta_t,c A_c) s + delta_t,c u_t,c B_t
                y_t,c = s . C_t + mb_d_c u_t,c
              m = y * SiLU(z)  (layer cross_from - 2's m is the MEMORY)
              out = m mb_out
      attention (diff): q = a wq + bq (H heads of hd), k, v = a wk + bk,
              a wv + bv (Hkv heads) — a cross layer takes k, v of the
              last self-attention layer (cross_from - 1)
              head i of H / 2: A1 = softmax(q_2i k_2j^T / sqrt(hd) + mask),
              A2 = softmax(q_2i+1 k_2j+1^T / sqrt(hd) + mask),
              j = i // ((H / 2) / (Hkv / 2)), the value [v_2j | v_2j+1];
              lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 .
              lambda_k2) + lambda_init, lambda_init = 0.8 - 0.6
              exp(-0.3 l);  o_i = RMSNorm(A1 v - lambda A2 v) subln_g
              (1 - lambda_init)  (eps 1e-5);  out = concat_i o_i wo + bo
              mask: causal; a window layer also t - s < sliding_window
      GMU:    out = (MEMORY * SiLU(a gmu_in)) gmu_out
      y = x + out;  x' = y + (SiLU(LN2(y) w_gate) * (LN2(y) w_up)) w_down
    logits = LN(x_L; lnf) embed^T                        (tied)

Attention is computed in blocks of queries and the output map in blocks of
the vocabulary, so that a 6 k-token sequence fits beside the weights.

``param_tree`` is the benchmark's ONE statement of that parameter tree;
``fixed_leaves`` the leaves that are set, not drawn (Mamba's A_log and the
step size's bias, as Mamba initialises them).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 512
V_BLOCKS = 8


def kinds(model):
    """Per layer: "mamba", "window", "full", "gmu" or "cross"."""
    out = []
    cross = model["cross_from"]
    for li in range(model["n_layers"]):
        mb = bool(model["mamba_layout"][li])
        if li >= cross:
            out.append("gmu" if mb else "cross")
        elif mb:
            out.append("mamba")
        else:
            out.append("window" if model["window_layout"][li] else "full")
    return out


_STACK = {"mamba": "mamba_layers", "window": "layers", "full": "layers",
          "gmu": "gmu_layers", "cross": "cross_layers"}


def _sizes(model):
    h = model["d_model"]
    di = model["mamba_expand"] * h
    return h, di, model["mamba_d_state"], model["mamba_dt_rank"]


def param_tree(model):
    """name -> (shape, kind) from the configuration file's ``model``
    sizes; layer stacks lead with (1, layers). ``kind`` is "ones" or a
    name :func:`init_std` gives the deviation of."""
    h, di, ns, r = _sizes(model)
    nh, kvh, hd, f = (model["n_heads"], model["n_kv_heads"],
                      model["head_dim"], model["d_ff"])
    count = dict((name, 0) for name in set(_STACK.values()))
    for kind in kinds(model):
        count[_STACK[kind]] += 1

    def base(n):
        return {"ln1_g": ((1, n, h), "ones"), "ln1_b": ((1, n, h), "normal"),
                "ln2_g": ((1, n, h), "ones"), "ln2_b": ((1, n, h), "normal"),
                "w_gate": ((1, n, h, f), "normal"),
                "w_up": ((1, n, h, f), "normal"),
                "w_down": ((1, n, f, h), "normal")}

    def attention(n, own_kv):
        out = dict(base(n), wq=((1, n, h, nh * hd), "qk"),
                   bq=((1, n, nh * hd), "normal"),
                   wo=((1, n, nh * hd, h), "normal"),
                   bo=((1, n, h), "normal"),
                   subln_g=((1, n, 2 * hd), "ones"))
        out.update(dict((name, ((1, n, hd), "lambda")) for name in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")))
        if own_kv:
            out.update(wk=((1, n, h, kvh * hd), "qk"),
                       bk=((1, n, kvh * hd), "normal"),
                       wv=((1, n, h, kvh * hd), "normal"),
                       bv=((1, n, kvh * hd), "normal"))
        return out

    n = count["mamba_layers"]
    mamba = dict(base(n), mb_in=((1, n, h, 2 * di), "normal"),
                 mb_conv=((1, n, model["mamba_d_conv"], di), "conv"),
                 mb_conv_b=((1, n, di), "normal"),
                 mb_x=((1, n, di, r + 2 * ns), "normal"),
                 mb_dt=((1, n, r, di), "dt"), mb_dt_b=((1, n, di), "dt_bias"),
                 mb_a_log=((1, n, di, ns), "a_log"),
                 mb_d=((1, n, di), "ones"),
                 mb_out=((1, n, di, h), "normal"))
    n = count["gmu_layers"]
    gmu = dict(base(n), gmu_in=((1, n, h, di), "normal"),
               gmu_out=((1, n, di, h), "normal"))
    return {"embed": ((model["vocab_size"], h), "normal"),
            "lnf_g": ((h,), "ones"), "lnf_b": ((h,), "normal"),
            "mamba_layers": mamba,
            "layers": attention(count["layers"], True),
            "gmu_layers": gmu,
            "cross_layers": attention(count["cross_layers"], False)}


def init_std(kind, model):
    """The deviation a random parameter of ``kind`` is drawn with (mean 0).
    "qk": the query and key maps, d_model ** -0.5, so that a head's
    scores have a deviation of about 1 at any width (0.0198 at the
    served 2560: random maps that left every softmax flat would hide
    lambda, the window and the pairing of heads from the comparison);
    "lambda": 0.1, as the Differential Transformer draws its lambda
    vectors; "conv": the taps, d_conv ** -0.5 (the convolution's default
    span); "dt": the step size's map, dt_rank ** -0.5 (Mamba's "random"
    initialisation, of uniform spread); "a_log" and "dt_bias" are set by
    :func:`fixed_leaves` (0 here). Everything else 0.02."""
    return {"qk": model["d_model"] ** -0.5, "lambda": 0.1,
            "conv": model["mamba_d_conv"] ** -0.5,
            "dt": model["mamba_dt_rank"] ** -0.5, "a_log": 0.0,
            "dt_bias": 0.0}.get(kind, INIT_STD)


def fixed_leaves(model):
    """``{(stack, name): float32 array}`` of the leaves Mamba SETS: A_log =
    log(1 .. N) on every channel (S4D-real), and the step size's bias the
    inverse softplus of steps spread log-evenly over [1e-3, 1e-1] across
    the channels, so that delta starts there."""
    _h, di, ns, _r = _sizes(model)
    n = sum(1 for k in kinds(model) if k == "mamba")
    a_log = np.broadcast_to(np.log(np.arange(1, ns + 1, dtype=np.float64)),
                            (1, n, di, ns))
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), di))
    bias = np.broadcast_to(dt + np.log(-np.expm1(-dt)), (1, n, di))
    return {("mamba_layers", "mb_a_log"): a_log.astype(np.float32),
            ("mamba_layers", "mb_dt_b"): bias.astype(np.float32)}


def with_fixed(params, model):
    """``params`` with :func:`fixed_leaves` set, in each leaf's type."""
    params = dict(params)
    for (stack, name), value in fixed_leaves(model).items():
        params[stack] = dict(params[stack])
        old = params[stack][name]
        params[stack][name] = jnp.asarray(value, old.dtype)
    return params


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _scan(u, delta, bm, cm, a, d):
    """The selective scan token by token: u, delta (s, di), bm, cm (s, N),
    a (di, N), d (di,) -> y (s, di)."""

    def step(state, xs):
        u_t, d_t, b_t, c_t = xs
        state = jnp.exp(d_t[:, None] * a) * state \
            + (d_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t + d * u_t

    _state, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                             (u, delta, bm, cm))
    return y


def _diff_attention(q, k, v, lam, window):
    """Both maps of every differential head, causal (and windowed): q (s,
    H, hd), k, v (s, Hkv, hd) -> A1 v - lam A2 v (s, H / 2, 2 hd), a block
    of queries at a time."""
    s, nh, hd = q.shape
    kvh = k.shape[1]
    rep = (nh // 2) // (kvh // 2)
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = (jnp.repeat(t, rep, axis=1) for t in (k[:, 0::2], k[:, 1::2]))
    vv = jnp.repeat(v.reshape(s, kvh // 2, 2 * hd), rep, axis=1)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    kpos = jnp.arange(s)

    def one(q0):
        qpos = q0 + jnp.arange(block)
        seen = qpos[:, None] >= kpos[None, :]
        if window is not None:
            seen = jnp.logical_and(seen, qpos[:, None] - kpos[None, :]
                                   < window)

        def attend(qm, km):
            qb = jax.lax.dynamic_slice_in_dim(qm, q0, block, 0)
            sc = jnp.einsum("qhd,lhd->hql", qb, km) / np.sqrt(hd)
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("hql,lhd->qhd", jax.nn.softmax(sc, -1), vv)

        return attend(q1, k1) - lam * attend(q2, k2)

    return jax.lax.map(one, jnp.arange(0, s, block)).reshape(s, nh // 2,
                                                             2 * hd)


@functools.partial(jax.jit, static_argnames=("sizes", "rows", "weights_as"))
def _forward(params, tokens, start, chosen, sizes, rows, weights_as):
    (layer_kinds, nh, kvh, hd, window, eps, taps, r, ns, lam_zero) = sizes
    f32 = jnp.float32

    def load(w):
        # the stored weight in float32; ``weights_as`` first rounds it to
        # a narrower type (what serving in that type would compute with)
        return (w if weights_as is None else w.astype(weights_as)) \
            .astype(f32)

    with jax.default_matmul_precision("highest"):
        x = load(params["embed"][tokens])
        s = x.shape[0]
        taken = dict((name, 0) for name in set(_STACK.values()))
        memory = shared = None
        for li, kind in enumerate(layer_kinds):
            name = _STACK[kind]
            lp = jax.tree_util.tree_map(
                lambda w, i=taken[name]: load(w[0, i]), params[name])
            taken[name] += 1
            a = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
            if kind == "mamba":
                uz = a @ lp["mb_in"]
                u, z = jnp.split(uz, 2, axis=-1)
                up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
                u = jax.nn.silu(sum(up[j:j + s] * lp["mb_conv"][j]
                                    for j in range(taps)) + lp["mb_conv_b"])
                dbc = u @ lp["mb_x"]
                delta = jax.nn.softplus(dbc[:, :r] @ lp["mb_dt"]
                                        + lp["mb_dt_b"])
                y = _scan(u, delta, dbc[:, r:r + ns], dbc[:, r + ns:],
                          -jnp.exp(lp["mb_a_log"]), lp["mb_d"])
                memory = y * jax.nn.silu(z)
                out = memory @ lp["mb_out"]
            elif kind == "gmu":
                out = (memory * jax.nn.silu(a @ lp["gmu_in"])) \
                    @ lp["gmu_out"]
            else:
                q = (a @ lp["wq"] + lp["bq"]).reshape(s, nh, hd)
                if kind == "cross":
                    k, v = shared
                else:
                    k = (a @ lp["wk"] + lp["bk"]).reshape(s, kvh, hd)
                    v = (a @ lp["wv"] + lp["bv"]).reshape(s, kvh, hd)
                    shared = k, v
                init = 0.8 - 0.6 * np.exp(-0.3 * li)
                lam = 0.0 if lam_zero else (
                    jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
                    - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]))
                    + init)
                o = _diff_attention(q, k, v, lam,
                                    window if kind == "window" else None)
                o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
                    * lp["subln_g"] * (1.0 - init)
                out = o.reshape(s, -1) @ lp["wo"] + lp["bo"]
            y = x + out
            m = _layer_norm(y, lp["ln2_g"], lp["ln2_b"], eps)
            x = y + (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) \
                @ lp["w_down"]
        x = _layer_norm(x, load(params["lnf_g"]), load(params["lnf_b"]), eps)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
        embed = params["embed"]
        if chosen is None:
            cuts = np.linspace(0, embed.shape[0], V_BLOCKS + 1).astype(int)
            return jnp.concatenate(
                [x @ load(embed[a:b]).T
                 for a, b in zip(cuts[:-1], cuts[1:])], axis=1)
        # the largest logit and the chosen token's, a block of the
        # vocabulary at a time: (rows, vocabulary) float32 is never whole
        width = embed.shape[0] // V_BLOCKS

        def block(carry, e):
            best, picked, first = carry
            logits = x @ load(e).T                        # (rows, width)
            at = chosen - first
            got = jnp.take_along_axis(
                logits, jnp.clip(at, 0, width - 1)[:, None], 1)[:, 0]
            inside = jnp.logical_and(at >= 0, at < width)
            return (jnp.maximum(best, logits.max(-1)),
                    jnp.where(inside, got, picked), first + width), None

        (best, picked, _), _ = jax.lax.scan(
            block, (jnp.full((rows,), -jnp.inf, f32), jnp.zeros((rows,), f32),
                    jnp.int32(0)),
            embed.reshape(V_BLOCKS, width, -1))
        return best - picked


def forward(params, tokens, model, pad_to=None, logits_from=0,
            logits_rows=None, weights_as=None):
    """float32 logits of positions ``logits_from ... logits_from +
    logits_rows`` (default: to the sequence's end). ``pad_to`` pads the
    sequence (everything is causal, so the padding changes nothing before
    it) so that sequences of many lengths share one compiled program; so
    does a fixed ``logits_rows`` (rows past the sequence's end are the
    padding's). ``weights_as`` (a dtype name) rounds every stored weight
    to that type before use: the reading of a precision below the served
    one, for setting a limit. A ``model`` with ``diff_lambda_zero`` (lambda
    0: ordinary attention on the first map), or with another
    ``window_layout``, is another model: what a program that broke that
    rule would compute."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    rows = int(logits_rows) if logits_rows else n - int(logits_from)
    start = min(int(logits_from), tokens.shape[0] - rows)
    logits = _forward(params, tokens, jnp.asarray(start, jnp.int32), None,
                      sizes=_static(model), rows=rows, weights_as=weights_as)
    return logits[int(logits_from) - start:]


def token_gaps(params, tokens, model, n_prompt, pad_to=None,
               weights_as=None):
    """For each token after the first ``n_prompt``: the largest logit the
    position before it gives, less that token's logit (0 where the token
    is the reference's own greedy choice), float32 — the logits' whole
    rows over the vocabulary are never held (200064 x 4 bytes a
    position). ``pad_to`` and ``weights_as`` as in :func:`forward`."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if pad_to is not None and pad_to > n:
        tokens = jnp.pad(tokens, (0, pad_to - n))
    if model["vocab_size"] % V_BLOCKS:
        raise ValueError("vocab_size %d is no multiple of %d blocks"
                         % (model["vocab_size"], V_BLOCKS))
    # position p's chosen token is the one at p + 1
    following = jnp.concatenate([tokens[1:], jnp.zeros((1,), jnp.int32)])
    gaps = _forward(params, tokens, jnp.asarray(0, jnp.int32), following,
                    sizes=_static(model), rows=tokens.shape[0],
                    weights_as=weights_as)
    return gaps[n_prompt - 1:n - 1]


def _static(model):
    """The sizes and rules ``_forward`` is compiled for."""
    return (tuple(kinds(model)), int(model["n_heads"]),
            int(model["n_kv_heads"]), int(model["head_dim"]),
            int(model["sliding_window"]), float(model["norm_eps"]),
            int(model["mamba_d_conv"]), int(model["mamba_dt_rank"]),
            int(model["mamba_d_state"]),
            bool(model.get("diff_lambda_zero", False)))
