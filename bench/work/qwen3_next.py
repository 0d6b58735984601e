"""Model FLOPs of the ``qwen3_next_80b_a3b`` configuration AS CUT (the
configuration file's ``model`` sizes): the multiply-adds a token NEEDS on
this chip, two operations each — in a Gated DeltaNet layer the fused
projections, the four-tap convolution, the recurrence's 3.5 multiply-adds a
state element (``bench/work/gdn_recurrent_step.py``: 7 operations) and the
output map; in a full layer the query-and-gate, key, value and output maps
and attention over the positions before it (a key and a value of head_dim
a query head a pair); in every layer the router (all ``num_experts``
scores), the shared expert with its gate, and the routed products of the
rows REALLY computed here (the caller gives the assignments that landed on
a held expert; the 448 absent experts' work is another chip's). What
padding, dummy slots, the chunked form's extra products or a kernel's
masked work cost is not counted: this is the numerator of a utilization."""


def _layers(m):
    n_lin = sum(1 for flag in m["linear_layout"][:m["n_layers"]] if flag)
    return n_lin, m["n_layers"] - n_lin


def _per_token_maps(m):
    """Multiply-adds of one token through every layer but its routed
    experts and its attention over the cache."""
    h = m["d_model"]
    n_lin, n_full = _layers(m)
    k_all = m["linear_key_heads"] * m["linear_key_dim"]
    v_all = m["linear_value_heads"] * m["linear_value_dim"]
    linear = (h * (2 * k_all + 2 * v_all) + h * 2 * m["linear_value_heads"]
              + m["linear_conv_width"] * (2 * k_all + v_all)
              + 3.5 * m["linear_value_heads"] * m["linear_key_dim"]
              * m["linear_value_dim"] + v_all * h)
    q_all = m["n_heads"] * m["head_dim"]
    full = h * 2 * q_all + 2 * h * m["n_kv_heads"] * m["head_dim"] \
        + q_all * h
    ffn = h * m["num_experts"] + 3 * h * m["moe_shared_width"] + h
    return n_lin * linear + n_full * full + m["n_layers"] * ffn


def _attended(m, seen):
    """Multiply-adds of attention over ``seen`` (query, key) pairs a full
    layer."""
    return _layers(m)[1] * m["n_heads"] * 2 * m["head_dim"] * seen


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
