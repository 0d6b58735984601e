"""Model FLOPs of the ``phi4_mini_flash_reasoning`` configuration (the
configuration file's ``model`` sizes): the multiply-adds a token NEEDS,
two operations each — in a Mamba layer the input map, the four-tap
convolution, the map to [delta | B | C], the step size's map, the scan's
3.5 multiply-adds a state element and the output map; in a self-attention
layer the q/k/v and output maps, in a cross-attention layer the q and
output maps, and in both differential attention over the positions before
the token (two maps a head: a head_dim score and a 2 x head_dim weighted
value each — the full layer and the cross layers over every position, the
window layers over their window); in a gated memory unit its two maps; in
every layer the SwiGLU FFN; the tied output map where logits are made. A
prompt NEEDS the cross-decoder for its last position alone (nothing of the
others is cached or read there). Padding, dummy slots and a kernel's
masked or zero-padded work are not counted: this is the numerator of a
utilization."""


def _kinds(m):
    out = []
    for li in range(m["n_layers"]):
        mb = bool(m["mamba_layout"][li])
        if li >= m["cross_from"]:
            out.append("gmu" if mb else "cross")
        elif mb:
            out.append("mamba")
        else:
            out.append("window" if m["window_layout"][li] else "full")
    return out


def _maps(m, kinds):
    """Multiply-adds of one token through ``kinds`` of layer, attention
    over the cache and logits aside."""
    h, f = m["d_model"], m["d_ff"]
    di = m["mamba_expand"] * h
    ns, r = m["mamba_d_state"], m["mamba_dt_rank"]
    q_all = m["n_heads"] * m["head_dim"]
    kv_all = m["n_kv_heads"] * m["head_dim"]
    per = {"mamba": h * 2 * di + m["mamba_d_conv"] * di
           + di * (r + 2 * ns) + r * di + 3.5 * ns * di + di * h,
           "window": h * (q_all + 2 * kv_all) + q_all * h,
           "gmu": 2 * h * di,
           "cross": 2 * h * q_all}
    per["full"] = per["window"]
    return sum(per[k] + 3 * h * f for k in kinds)


def _attended(m, seen_full, seen_window, kinds):
    """Multiply-adds of differential attention over ``seen_*`` (query,
    key) pairs of a full-or-cross layer and of a window layer."""
    per_pair = (m["n_heads"] // 2) * 6 * m["head_dim"]
    full = sum(1 for k in kinds if k in ("full", "cross"))
    window = sum(1 for k in kinds if k == "window")
    return per_pair * (full * seen_full + window * seen_window)


def token_flops(m, pos):
    """One decoded token at position ``pos``, its logits included."""
    kinds = _kinds(m)
    return 2 * (_maps(m, kinds)
                + _attended(m, pos + 1, min(pos + 1, m["sliding_window"]),
                            kinds)
                + m["d_model"] * m["vocab_size"])


def prefill_flops(m, prompt_len):
    """A prompt: the self-decoder over its positions 0 .. prompt_len - 1,
    the cross-decoder and the logits over its last."""
    kinds = _kinds(m)
    own = kinds[:m["cross_from"]]
    cross = kinds[m["cross_from"]:]
    w = m["sliding_window"]
    window_pairs = (prompt_len * (prompt_len + 1) // 2 if prompt_len <= w
                    else w * (w + 1) // 2 + (prompt_len - w) * w)
    return 2 * (prompt_len * _maps(m, own)
                + _attended(m, prompt_len * (prompt_len + 1) // 2,
                            window_pairs, own)
                + _maps(m, cross) + _attended(m, prompt_len, 0, cross)
                + m["d_model"] * m["vocab_size"])
