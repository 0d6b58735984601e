"""Model FLOPs of the ``deepseek_v3`` configuration AS CUT (the
configuration file's ``model`` sizes): the multiply-adds a token NEEDS on
this chip, two operations each — the latent attention's maps, attention
over the positions before it, the dense FFN of a leading layer, and in an
expert layer the router (all ``num_experts`` scores), the shared expert and
the routed products of the rows REALLY computed here (the caller gives the
assignments that landed on a held expert; the 240 absent experts' work is
another chip's). Attention is counted as the equations are written
(decompressed: keys of nope + rope, values of v, a head) — the fewest
operations that compute it; the decode kernel's absorbed form does more and
is measured against its own count (``bench/work/mla_paged_decode.py``).
What padding, dummy slots or a kernel's masked work cost is not counted:
this is the numerator of a utilization."""


def _attention_maps(m):
    h, nh = m["d_model"], m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
            + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                        + m["v_head_dim"])
            + nh * m["v_head_dim"] * h)


def _per_token_maps(m):
    """Multiply-adds of one token through every layer but its routed
    experts and its attention over the cache."""
    h, dense = m["d_model"], m["dense_layers"]
    moe = m["n_layers"] - dense
    return (m["n_layers"] * _attention_maps(m)
            + dense * 3 * h * m["d_ff_dense"]
            + moe * (h * m["num_experts"] + 3 * h * m["moe_shared_width"]))


def _attended(m, seen):
    """Multiply-adds of attention over ``seen`` (query, key) pairs a layer."""
    per_pair = m["n_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                               + m["v_head_dim"])
    return m["n_layers"] * per_pair * seen


def routed_flops(m, rows):
    """``rows``: (token, held expert) assignments computed, all layers."""
    return 2 * 3 * rows * m["d_model"] * m["d_ff"]


def token_flops(m, pos):
    """One decoded token at position ``pos``, its logits included, without
    its routed experts."""
    return 2 * (_per_token_maps(m) + _attended(m, pos + 1)
                + m["d_model"] * m["vocab_size"])


def prefill_flops(m, prompt_len):
    """A prompt's positions 0 .. prompt_len - 1 and the logits of the
    last, without its routed experts."""
    return 2 * (prompt_len * _per_token_maps(m)
                + _attended(m, prompt_len * (prompt_len + 1) // 2)
                + m["d_model"] * m["vocab_size"])
