"""Model FLOPs of the ``smallthinker_21b_a3b`` block (the configuration
file's ``model`` sizes): the multiply-adds a token NEEDS, two operations
each — its projections, the router, its ``moe_top_k`` experts, attention
over the positions its layer's rule lets it see, and the output map where
a token's logits are made. What padding, dummy slots or a kernel's masked
work cost is not counted: this is the numerator of a utilization."""


def _per_token_maps(model):
    h, hd = model["d_model"], model["head_dim"]
    dq, dkv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    attn = h * dq + 2 * h * dkv + dq * h
    experts = model["moe_top_k"] * 3 * h * model["d_ff"]
    return 2 * (attn + h * model["num_experts"] + experts)


def _seen(model, layer, pos):
    """Positions a token at ``pos`` attends in ``layer`` (itself too)."""
    if model["window_layout"][layer]:
        return min(pos + 1, model["sliding_window"])
    return pos + 1


def token_flops(model, pos, with_logits):
    """One token at position ``pos`` through every layer."""
    hd_all = model["n_heads"] * model["head_dim"]
    attention = sum(4 * hd_all * _seen(model, layer, pos)
                    for layer in range(model["n_layers"]))
    head = 2 * model["d_model"] * model["vocab_size"] if with_logits else 0
    return model["n_layers"] * _per_token_maps(model) + attention + head


def prefill_flops(model, prompt_len):
    """A prompt's positions 0 .. prompt_len - 1; logits for the last."""
    hd_all = model["n_heads"] * model["head_dim"]
    total = prompt_len * model["n_layers"] * _per_token_maps(model)
    for layer in range(model["n_layers"]):
        if model["window_layout"][layer]:
            w = min(prompt_len, model["sliding_window"])
            seen = w * (w + 1) // 2 + (prompt_len - w) * w
        else:
            seen = prompt_len * (prompt_len + 1) // 2
        total += 4 * hd_all * seen
    return total + 2 * model["d_model"] * model["vocab_size"]
