"""Transformer LM with fully-composed 5D parallelism (dp/sp/tp/pp/ep).

The reference's long-sequence story is bucketing + fused RNNs and its
only parallelism is data-parallel KVStore + manual group2ctx placement
(SURVEY.md §2.3/§5). This module is the TPU-first replacement: ONE
``shard_map`` over a 5-axis ``Mesh`` runs a GPT-style decoder with

* **dp** — batch sharding; gradient psum over ICI;
* **sp** — sequence sharding with ring attention (``lax.ppermute``
  K/V rotation, online softmax — see parallel/ring_attention.py);
* **tp** — Megatron-style tensor parallelism: Q/K/V/FFN-up sharded on
  the output dim (heads split), out-proj/FFN-down sharded on the input
  dim, one psum per residual branch;
* **pp** — GPipe microbatch pipeline between stage-sharded layer
  stacks (``lax.scan`` schedule + ppermute handoff);
* **ep** — optional MoE FFN with experts sharded over ``ep`` and
  MXU-friendly one-hot dispatch/combine (parallel/moe.py math).

Everything is manual-collective SPMD: the whole train step (forward,
backward, SGD update, all reductions) compiles to a single XLA program
per device. Size-1 axes degrade to identity collectives, so the same
code runs any slice of the 5D configuration.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..ops.pallas.flash_attention import causal_mask as _causal_mask, on_tpu
from .ring_attention import _ring_attention_local
from .moe import group_limited_routing, moe_ffn_sorted, top_k_gating

__all__ = ["TransformerConfig", "init_transformer_params",
           "make_transformer_train_step", "transformer_forward_single",
           "init_kv_cache", "init_kv_pages", "PagedKVCache",
           "HybridKVCache", "LatentKVCache", "LinearStateCache",
           "kv_layer_kinds", "paged_cache",
           "cache_pools",
           "transformer_decode_step", "transformer_decode_step_paged",
           "transformer_prefill", "transformer_prefill_paged",
           "transformer_generate"]

AXES = ("dp", "sp", "tp", "pp", "ep")


def _kv_heads(cfg):
    return cfg.n_kv_heads or cfg.n_heads


def _head_dim(cfg):
    return cfg.head_dim or cfg.d_model // cfg.n_heads


def _is_mla(cfg):
    """Latent attention (MLA): the layer caches one compressed vector a
    token and no per-head K and V."""
    return bool(cfg.kv_lora_rank)


def _drop_free(cfg):
    """The sorted, drop-free expert layer (any router but "capacity")."""
    return bool(cfg.num_experts) and cfg.moe_router != "capacity"


def _moe_first(cfg):
    """The first expert this device holds (``moe_local_experts``)."""
    return int(cfg.moe_local_experts[0]) if cfg.moe_local_experts else 0


def _moe_held(cfg):
    """How many of the router's ``num_experts`` this device holds."""
    return (int(cfg.moe_local_experts[1]) if cfg.moe_local_experts
            else cfg.num_experts)


def _has_linear(cfg):
    """Whether some layer is a linear-attention (Gated DeltaNet) layer."""
    return cfg.linear_layout is not None and any(
        cfg.linear_layout[:cfg.n_layers])


def _mamba_at(cfg, li):
    """Whether ``mamba_layout`` marks layer ``li``."""
    return cfg.mamba_layout is not None and bool(cfg.mamba_layout[li])


def _has_state(cfg):
    """Whether some layer keeps a fixed-size recurrent state a sequence
    (a Gated DeltaNet or a Mamba layer): such a model's cache is a
    :class:`LinearStateCache`."""
    return _has_linear(cfg) or any(
        k == "mamba" for k in kv_layer_kinds(cfg))


def _yoco_sources(cfg):
    """``(kv layer, memory layer)`` of a model with a cross-decoder
    (``cross_from``): the last self-attention layer before it, whose K/V
    every cross-attention layer reads, and the last Mamba layer before
    it, whose scan output every gated memory unit reads."""
    before = [_layer_rule(cfg, li)[0] for li in range(cfg.cross_from)]
    kv = max(li for li, k in enumerate(before) if k in ("full", "window"))
    mem = max([li for li, k in enumerate(before) if k == "mamba"] or [-1])
    return kv, mem


def _yarn_mscale(cfg):
    """YaRN's attention temperature ``0.1 mscale_all_dim ln(factor) + 1``
    (1 without ``rope_scaling``): the softmax scale carries its square."""
    rs = cfg.rope_scaling
    if not rs or rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs.get("mscale_all_dim", 0) * math.log(rs["factor"]) + 1.0


def _rope_inv_freq(cfg, dim):
    """(dim / 2,) float32 rotation frequencies: ``rope_base ** (-2i /
    dim)``, or under ``rope_scaling`` (YaRN, arXiv:2309.00071) the blend
    of those and their ``factor``-fold interpolation: dimensions that
    turn more than ``beta_fast`` times over the original length keep
    theirs, those that turn less than ``beta_slow`` times are
    interpolated, a linear ramp between."""
    i = np.arange(dim // 2, dtype=np.float64)
    freq = cfg.rope_base ** (-2.0 * i / dim)
    rs = cfg.rope_scaling
    if not rs:
        return freq.astype(np.float32)

    def turns_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_base))

    low = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0, 1)
    return (freq / rs["factor"] * ramp + freq * (1 - ramp)).astype(
        np.float32)


def _layer_rule(cfg, li):
    """``(kind, rotary, window)`` of layer ``li``: ``"linear"`` where
    ``linear_layout`` marks a Gated DeltaNet layer (no keys, values,
    rotation or window: a fixed-size state a sequence); from layer
    ``cross_from`` on (a cross-decoder) ``"gmu"`` where ``mamba_layout``
    marks the layer (a gated memory unit) and ``"cross"`` elsewhere
    (cross-attention over another layer's K/V): neither owns a cache;
    before it ``"mamba"`` where ``mamba_layout`` marks a Mamba layer (a
    fixed-size state a sequence); else whether q/k
    rotate there (``rope_layout``; every layer of a "rope" model without
    one) and how far back it attends (``sliding_window`` where
    ``window_layout`` marks the layer, or everywhere without a layout;
    None = to the start), its kind ``"window"`` or ``"full"``. The one
    place a layer's kind is decided."""
    if cfg.linear_layout is not None and bool(cfg.linear_layout[li]):
        return "linear", False, None
    if cfg.cross_from is not None and li >= cfg.cross_from:
        return ("gmu" if _mamba_at(cfg, li) else "cross"), False, None
    if _mamba_at(cfg, li):
        return "mamba", False, None
    rotary = cfg.pos_type == "rope" and (
        cfg.rope_layout is None or bool(cfg.rope_layout[li]))
    windowed = cfg.sliding_window is not None and (
        cfg.window_layout is None or bool(cfg.window_layout[li]))
    return (("window", rotary, int(cfg.sliding_window)) if windowed
            else ("full", rotary, None))


def kv_layer_kinds(cfg):
    """Per layer, ``"linear"`` or ``"mamba"`` where the layer keeps a
    recurrent state and no keys or values, ``"gmu"`` or ``"cross"`` where
    it keeps nothing (a cross-decoder layer reads what an earlier layer
    left), ``"window"`` where it attends a sliding window (its cache may
    forget older positions), else ``"full"``."""
    return tuple(_layer_rule(cfg, li)[0] for li in range(cfg.n_layers))


def _expand_kv(t, groups, head_axis):
    """Repeat each K/V head ``groups`` times along ``head_axis`` so
    grouped K/V line up with the query heads (GQA -> MHA view)."""
    return t if groups == 1 else jnp.repeat(t, groups, axis=head_axis)


def _validate_config(cfg):
    for name in ("window_layout", "rope_layout", "linear_layout",
                 "mamba_layout"):
        layout = getattr(cfg, name)
        if layout is not None and len(layout) < cfg.n_layers:
            raise ValueError("%s has %d entries for n_layers=%d"
                             % (name, len(layout), cfg.n_layers))
    kvh = cfg.n_kv_heads
    if kvh is not None:
        if not isinstance(kvh, int) or kvh < 1:
            raise ValueError("n_kv_heads must be a positive int, got %r"
                             % (kvh,))
        if cfg.n_heads % kvh:
            raise ValueError("n_heads=%d must divide by n_kv_heads=%d"
                             % (cfg.n_heads, kvh))
    for name, allowed in (("norm", ("layernorm", "rmsnorm")),
                          ("moe_router", ("capacity", "topk", "noaux_tc")),
                          ("moe_router_input", ("ffn", "layer")),
                          ("gate_act", ("relu", "silu"))):
        if getattr(cfg, name) not in allowed:
            raise ValueError("%s=%r is not one of %s"
                             % (name, getattr(cfg, name), allowed))
    if cfg.moe_router != "capacity" and not cfg.num_experts:
        raise ValueError("moe_router=%r needs num_experts > 0"
                         % cfg.moe_router)
    if cfg.moe_router_input == "layer" and cfg.moe_router != "topk":
        raise ValueError("moe_router_input='layer' needs "
                         "moe_router='topk'")
    if _is_mla(cfg):
        for name in ("q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                     "v_head_dim"):
            if getattr(cfg, name) < 1:
                raise ValueError("kv_lora_rank=%d (latent attention) "
                                 "needs %s > 0" % (cfg.kv_lora_rank, name))
        if cfg.sliding_window is not None or cfg.n_kv_heads is not None:
            raise ValueError("latent attention has one shared latent and "
                             "no window: n_kv_heads and sliding_window "
                             "must be None")
    rs = cfg.rope_scaling
    if rs and rs.get("type") != "yarn":
        raise ValueError("rope_scaling type %r: only 'yarn' is computed"
                         % (rs.get("type"),))
    if rs and rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
        raise ValueError("rope_scaling mscale=%r != mscale_all_dim=%r: "
                         "their ratio would scale cos and sin, which is "
                         "not computed (the published models have them "
                         "equal)" % (rs.get("mscale", 1),
                                     rs.get("mscale_all_dim", 0)))
    if not 0 <= cfg.dense_layers <= cfg.n_layers:
        raise ValueError("dense_layers=%d of n_layers=%d"
                         % (cfg.dense_layers, cfg.n_layers))
    if cfg.dense_layers and not (_drop_free(cfg) and cfg.d_ff_dense > 0):
        raise ValueError("dense_layers=%d leading gated FFN layers need "
                         "d_ff_dense > 0 and a drop-free expert layer "
                         "after them" % cfg.dense_layers)
    if (cfg.moe_shared_width or cfg.moe_local_experts) \
            and not _drop_free(cfg):
        raise ValueError("moe_shared_width / moe_local_experts belong to "
                         "the drop-free expert layer (moe_router 'topk' or "
                         "'noaux_tc')")
    if cfg.moe_router == "noaux_tc" and (
            cfg.num_experts % cfg.moe_n_groups
            or not 1 <= cfg.moe_topk_groups <= cfg.moe_n_groups):
        raise ValueError("noaux_tc: %d experts in %d groups, %d kept"
                         % (cfg.num_experts, cfg.moe_n_groups,
                            cfg.moe_topk_groups))
    if cfg.moe_local_experts and not (
            0 <= _moe_first(cfg) and _moe_held(cfg) >= 1
            and _moe_first(cfg) + _moe_held(cfg) <= cfg.num_experts):
        raise ValueError("moe_local_experts=%r (first, count) is not a "
                         "run of the %d experts"
                         % (cfg.moe_local_experts, cfg.num_experts))
    if _has_linear(cfg):
        for name in ("linear_key_heads", "linear_value_heads",
                     "linear_key_dim", "linear_value_dim"):
            if getattr(cfg, name) < 1:
                raise ValueError("linear_layout (Gated DeltaNet layers) "
                                 "needs %s > 0" % name)
        if cfg.linear_value_heads % cfg.linear_key_heads:
            raise ValueError("linear_value_heads=%d must divide by "
                             "linear_key_heads=%d"
                             % (cfg.linear_value_heads,
                                cfg.linear_key_heads))
        if cfg.linear_conv_width < 2:
            raise ValueError("linear_conv_width=%d: the causal convolution "
                             "is at least 2 wide" % cfg.linear_conv_width)
        if _is_mla(cfg) or cfg.sliding_window is not None \
                or cfg.dense_layers:
            raise ValueError("linear_layout beside latent attention, a "
                             "sliding window or dense_layers is not "
                             "computed: the state cache stands beside ONE "
                             "paged pool of full-attention layers")
    if cfg.mamba_layout is not None:
        for name in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                     "mamba_dt_rank"):
            if getattr(cfg, name) < 1:
                raise ValueError("mamba_layout (Mamba layers) needs %s > 0"
                                 % name)
        if cfg.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv=%d: the causal convolution is "
                             "at least 2 wide" % cfg.mamba_d_conv)
        if _has_linear(cfg) or _is_mla(cfg) or cfg.num_experts \
                or cfg.dense_layers:
            raise ValueError("mamba_layout beside linear_layout, latent "
                             "attention, experts or dense_layers is not "
                             "computed")
    if cfg.cross_from is not None:
        if not 0 < cfg.cross_from < cfg.n_layers:
            raise ValueError("cross_from=%r of n_layers=%d"
                             % (cfg.cross_from, cfg.n_layers))
        kinds = [_layer_rule(cfg, li)[0] for li in range(cfg.n_layers)]
        if kinds[cfg.cross_from - 1] != "full":
            raise ValueError("cross_from=%d: the layer before the "
                             "cross-decoder keeps the K/V its cross layers "
                             "read, so it must be a full-attention layer"
                             % cfg.cross_from)
        if "gmu" in kinds and "mamba" not in kinds[:cfg.cross_from]:
            raise ValueError("a gated memory unit reads the scan output "
                             "of a Mamba layer before cross_from")
    if cfg.diff_attn:
        kvh = _kv_heads(cfg)
        if cfg.n_heads % 2 or kvh % 2 or (cfg.n_heads // 2) % (kvh // 2):
            raise ValueError("diff_attn pairs query heads and K/V heads: "
                             "n_heads=%d and n_kv_heads=%d must be even, "
                             "the pairs of one a multiple of the other's"
                             % (cfg.n_heads, kvh))
        if _is_mla(cfg) or cfg.pos_type == "rope" or cfg.qk_norm \
                or cfg.attn_gate:
            raise ValueError("diff_attn is computed without rotation, "
                             "q/k norms, gates or latent attention")
    rot = _head_dim(cfg) * cfg.rotary_share
    if not 0 < cfg.rotary_share <= 1 or cfg.pos_type == "rope" and (
            rot != int(rot) or int(rot) % 2):
        raise ValueError("rotary_share=%r of head_dim=%d is not an even "
                         "number of dimensions"
                         % (cfg.rotary_share, _head_dim(cfg)))
    if _is_mla(cfg) and (cfg.qk_norm or cfg.attn_gate
                         or cfg.rotary_share != 1.0):
        raise ValueError("qk_norm, attn_gate and rotary_share belong to "
                         "the per-head K/V attention, not to latent "
                         "attention")
    if cfg.norm_zero_centered and cfg.norm != "rmsnorm":
        raise ValueError("norm_zero_centered (gain 1 + w) is an RMSNorm's")
    if cfg.moe_shared_gate and not cfg.moe_shared_width:
        raise ValueError("moe_shared_gate needs a shared expert "
                         "(moe_shared_width > 0)")


# what the shard_map training block (``_block_local``) computes, by
# field: a config that asks for anything else is refused by name
_TRAINABLE = {"head_dim": None, "norm": "layernorm",
              "tie_embeddings": True, "moe_router": "capacity",
              "moe_router_input": "ffn", "sliding_window": None,
              "window_layout": None, "rope_layout": None,
              "kv_lora_rank": 0, "q_lora_rank": 0, "qk_nope_head_dim": 0,
              "qk_rope_head_dim": 0, "v_head_dim": 0, "rope_scaling": None,
              "dense_layers": 0, "d_ff_dense": 0, "gate_act": "relu",
              "moe_shared_width": 0, "moe_n_groups": 1,
              "moe_topk_groups": 1, "moe_routed_scale": 1.0,
              "moe_local_experts": None, "linear_layout": None,
              "linear_key_heads": 0, "linear_value_heads": 0,
              "linear_key_dim": 0, "linear_value_dim": 0,
              "linear_conv_width": 0, "rotary_share": 1.0,
              "qk_norm": False, "attn_gate": False,
              "norm_zero_centered": False, "moe_shared_gate": False,
              "mamba_layout": None, "mamba_d_state": 0, "mamba_d_conv": 0,
              "mamba_expand": 0, "mamba_dt_rank": 0, "cross_from": None,
              "diff_attn": False, "attn_bias": False, "ffn_gated": False}


def _validate_trainable(cfg):
    for name, only in _TRAINABLE.items():
        if getattr(cfg, name) != only:
            raise ValueError(
                "make_transformer_train_step cannot run %s=%r: the "
                "sharded training block computes %s=%r only (the "
                "single-device forward, prefill and decode paths run "
                "it)" % (name, getattr(cfg, name), name, only))


def _rope(t, positions, base):
    """Rotary position embedding over the trailing head_dim: pairs
    (even, odd) rotate by position-scaled angles. t: (..., S, hd) with
    positions (S,) broadcastable against the seq axis."""
    hd = t.shape[-1]
    half = hd // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs    # (S, half)
    cos = jnp.cos(ang).astype(t.dtype)
    sin = jnp.sin(ang).astype(t.dtype)
    t1 = t[..., :half]
    t2 = t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin,
                            t1 * sin + t2 * cos], axis=-1)


@dataclass
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    # grouped-query attention: number of shared K/V heads (None = MHA).
    # Shrinks the KV cache by n_heads/n_kv_heads — the long-context
    # decode memory lever (n_kv_heads=1 is multi-query attention).
    n_kv_heads: int = None
    n_layers: int = 4
    d_ff: int = 256
    max_len: int = 512
    num_experts: int = 0          # 0 = dense FFN; >0 = MoE FFN
    moe_top_k: int = 2
    capacity_factor: float = 2.0
    dtype: object = jnp.float32
    sp_attn: str = "ring"         # "ring" (ppermute) | "ulysses" (a2a)
    remat: bool = False           # jax.checkpoint each block (long-seq)
    # position encoding: "learned" adds a trained table; "rope" rotates
    # q/k per head-dim pair (no length-bound table — the long-context
    # default; extrapolates past training length)
    pos_type: str = "learned"
    rope_base: float = 10000.0
    # -- the block's other shapes; each default is the GPT-2 block ------
    head_dim: int = None          # None = d_model // n_heads
    norm: str = "layernorm"       # | "rmsnorm" (a gain, no shift)
    norm_eps: float = 1e-5
    tie_embeddings: bool = True   # False: an output map of its own
    # "capacity": top-2 one-hot dispatch that drops what overflows, over
    # two-matrix GELU experts; "topk": moe_top_k of E, softmax over the
    # chosen, rows sorted by expert, grouped products over gated ReLU
    # experts relu(x Wg) * (x Wu) Wd, nothing dropped (parallel/moe.py)
    moe_router: str = "capacity"
    # what the router reads: the FFN's normalised input, or ("layer")
    # the residual stream as it enters the layer, before attention
    moe_router_input: str = "ffn"
    # per-layer kinds: a layer with window_layout[l] attends the last
    # sliding_window positions only; one with rope_layout[l] rotates
    # q/k (None = every layer does what pos_type / sliding_window say)
    sliding_window: int = None
    window_layout: tuple = None
    rope_layout: tuple = None
    # -- latent attention (MLA): kv_lora_rank > 0 turns it on. Queries are
    # compressed to q_lora_rank, keys and values to kv_lora_rank (what
    # the cache holds, with ONE rotated key of qk_rope_head_dim for all
    # heads); a head's key is qk_nope_head_dim + qk_rope_head_dim wide,
    # its value v_head_dim (n_heads of them; head_dim is not read)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN, as the published config states it: {"type": "yarn", "factor",
    # "beta_fast", "beta_slow", "original_max_position_embeddings",
    # "mscale", "mscale_all_dim"} — blended frequencies, and mscale ** 2
    # on the softmax scale
    rope_scaling: dict = None
    # -- two kinds of FFN in one model: the first dense_layers layers have
    # a dense gated FFN of width d_ff_dense (params["dense_layers"], a
    # stack of its own), the rest the expert layer (params["layers"])
    dense_layers: int = 0
    d_ff_dense: int = 0
    # the gate of every gated FFN (experts, shared expert, dense_layers)
    gate_act: str = "relu"        # | "silu"
    # a shared expert of this width beside the routed ones (0 = none)
    moe_shared_width: int = 0
    # moe_router "noaux_tc" (parallel/moe.py:group_limited_routing):
    # sigmoid scores, a bias in the choice, moe_n_groups groups of which
    # moe_topk_groups stay, weights normalised and times moe_routed_scale
    moe_n_groups: int = 1
    moe_topk_groups: int = 1
    moe_routed_scale: float = 1.0
    # (first, count): the experts of the router's num_experts THIS device
    # holds (None = all). The router scores them all; the layer computes
    # its own experts' part of the sum (and the shared expert)
    moe_local_experts: tuple = None
    # the shared expert's output times sigmoid(x w), w (d, 1)
    moe_shared_gate: bool = False
    # -- the per-head K/V attention's other shapes ---------------------------
    # rotate only the first head_dim * rotary_share dimensions of q and k
    rotary_share: float = 1.0
    # an RMSNorm over head_dim on every head's q and k before the rotation
    qk_norm: bool = False
    # wq makes a gate beside each head's query ([query ; gate], 2 *
    # head_dim a head); the attention's output is times sigmoid(gate)
    attn_gate: bool = False
    # every RMSNorm's gain is (1 + w), computed in float32
    norm_zero_centered: bool = False
    # -- linear attention (Gated DeltaNet, arXiv:2412.06464): a layer with
    # linear_layout[l] has no keys or values but a recurrent state of
    # linear_value_heads matrices (linear_key_dim, linear_value_dim) and
    # the last linear_conv_width - 1 inputs of a causal depthwise
    # convolution over its q, k, v channels (params["linear_layers"], a
    # stack of its own; the other layers are params["layers"])
    linear_layout: tuple = None
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_width: int = 0
    # -- a decoder-hybrid-decoder (SambaY, arXiv:2507.06607). Before layer
    # cross_from, a layer with mamba_layout[l] is a Mamba-1 layer (the
    # selective scan, arXiv:2312.00752): mamba_expand * d_model channels
    # of mamba_d_state states each, a causal depthwise convolution
    # mamba_d_conv wide, a step size through a rank-mamba_dt_rank map
    # (params["mamba_layers"]). From layer cross_from on (YOCO's cross-
    # decoder, arXiv:2405.05254) a mamba_layout layer is a gated memory
    # unit over the last Mamba layer's scan output (params["gmu_layers"])
    # and any other a cross-attention layer over the K/V of the full
    # layer just before cross_from (params["cross_layers"])
    mamba_layout: tuple = None
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    cross_from: int = None
    # differential attention (arXiv:2410.05258) in every attention layer:
    # query heads (2i, 2i + 1) and K heads (2j, 2j + 1) pair up, a pair's
    # two V heads make one value twice as wide, and head i's output is
    # RMSNorm((softmax_1 - lambda softmax_2) v) (1 - lambda_init)
    diff_attn: bool = False
    # biases on the attention's q/k/v maps and its output map
    attn_bias: bool = False
    # the dense FFN as (act(x W_gate) * (x W_up)) W_down, act = gate_act
    ffn_gated: bool = False


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _param_specs(cfg, pp):
    """PartitionSpecs per parameter (layer stacks lead with a pp axis)."""
    lyr = {
        "ln1_g": P("pp", None, None), "ln1_b": P("pp", None, None),
        "ln2_g": P("pp", None, None), "ln2_b": P("pp", None, None),
    }
    if _is_mla(cfg):
        lyr.update(dict((name, P("pp", None, None, None))
                        for name in _MLA_MAPS))
        lyr.update({"q_ln_g": P("pp", None, None),
                    "kv_ln_g": P("pp", None, None)})
    else:
        lyr.update({
            "wq": P("pp", None, None, "tp"), "wk": P("pp", None, None, "tp"),
            "wv": P("pp", None, None, "tp"),
            "wo": P("pp", None, "tp", None)})
        if cfg.qk_norm:
            lyr.update({"q_norm_g": P("pp", None, None),
                        "k_norm_g": P("pp", None, None)})
        if cfg.attn_bias:
            lyr.update(dict((name, P("pp", None, None))
                            for name in ("bq", "bk", "bv", "bo")))
        if cfg.diff_attn:
            lyr.update(dict((name, P("pp", None, None))
                            for name in _DIFF_PARAMS))
    mixer = set(lyr) - {"ln1_g", "ln1_b", "ln2_g", "ln2_b"}
    dense = dict(lyr)                  # a leading dense layer's (below)
    if cfg.num_experts:
        lyr["gate"] = P("pp", None, None, None)
        for name in _expert_names(cfg):
            lyr[name] = P("pp", None, "ep", None, None)
        if cfg.moe_router == "noaux_tc":
            lyr["gate_bias"] = P("pp", None, None)
        if cfg.moe_shared_width:
            for name in _GATED:
                lyr["ws_" + name] = P("pp", None, None, None)
        if cfg.moe_shared_gate:
            lyr["ws_sigmoid"] = P("pp", None, None, None)
    elif cfg.ffn_gated:
        for name in _GATED:
            lyr["w_" + name] = P("pp", None, None, None)
    else:
        lyr.update({"w1": P("pp", None, None, "tp"),
                    "w2": P("pp", None, "tp", None)})
    specs = {
        "embed": P(None, None),
        "lnf_g": P(None,), "lnf_b": P(None,),
        "layers": lyr,
    }
    if cfg.dense_layers:
        for name in _GATED:
            dense["w_" + name] = P("pp", None, None, None)
        specs["dense_layers"] = dense
    if cfg.norm == "rmsnorm":
        for stack in (lyr, dense):
            for name in ("ln1_b", "ln2_b"):
                del stack[name]
        del specs["lnf_b"]
    if _has_linear(cfg):
        # a linear layer: the block's norms and FFN, and in place of the
        # attention's maps a Gated DeltaNet's
        specs["linear_layers"] = dict(
            [(k, v) for k, v in lyr.items() if k not in mixer],
            **dict((name, P(*("pp",) + (None,) * (rank + 1)))
                   for name, rank in _GDN_PARAMS))
    rest = dict((k, v) for k, v in lyr.items() if k not in mixer)
    kinds = kv_layer_kinds(cfg)
    for kind, stack, maps in (("mamba", "mamba_layers", _MAMBA_PARAMS),
                              ("gmu", "gmu_layers", _GMU_PARAMS)):
        if kind in kinds:
            specs[stack] = dict(rest, **dict(
                (name, P(*("pp",) + (None,) * (rank + 1)))
                for name, rank in maps))
    if "cross" in kinds:
        # a cross-attention layer: the queries and the output map of the
        # self-attention's, no keys or values of its own
        specs["cross_layers"] = dict(rest, **dict(
            (k, v) for k, v in lyr.items()
            if k in mixer and k not in ("wk", "wv", "bk", "bv")))
    if not cfg.tie_embeddings:
        specs["head"] = P(None, None)
    if cfg.pos_type == "learned":
        specs["pos"] = P(None, None)
    return specs


_MLA_MAPS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
# a Gated DeltaNet layer's parameters with their ranks behind (pp, layers):
# the fused projections [q | k | v | z] and [b | a], the depthwise
# convolution's taps (width, channels), the decay's A_log and dt_bias a
# value head, the gated norm's gain over a value head, the output map
_GDN_PARAMS = (("gdn_qkvz", 2), ("gdn_ba", 2), ("gdn_conv", 2),
               ("gdn_a_log", 1), ("gdn_dt_bias", 1), ("gdn_norm_g", 1),
               ("gdn_out", 2))
_GATED = ("gate", "up", "down")        # the three maps of a gated FFN
# a Mamba layer's parameters with their ranks behind (pp, layers): the
# input map [x | z], the depthwise convolution's taps (width, channels)
# and bias, the map to [delta | B | C], the step size's map and bias,
# A_log (channels, d_state), the skip D, the output map
_MAMBA_PARAMS = (("mb_in", 2), ("mb_conv", 2), ("mb_conv_b", 1),
                 ("mb_x", 2), ("mb_dt", 2), ("mb_dt_b", 1), ("mb_a_log", 2),
                 ("mb_d", 1), ("mb_out", 2))
# a gated memory unit's two maps
_GMU_PARAMS = (("gmu_in", 2), ("gmu_out", 2))
# differential attention's lambda vectors (head_dim each) and the gain of
# its RMSNorm over a pair's value (2 * head_dim)
_DIFF_PARAMS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                "subln_g")


# the parameter stacks a model may have, and the kind of layer each holds
_STACKS = ("layers", "dense_layers", "linear_layers", "mamba_layers",
           "gmu_layers", "cross_layers")
_STACK_OF = {"linear": "linear_layers", "mamba": "mamba_layers",
             "gmu": "gmu_layers", "cross": "cross_layers"}


def _expert_names(cfg):
    """The stacked expert maps of a layer: gate, up and down of the
    drop-free routers' gated experts, or the two of a GELU one."""
    return (("we_gate", "we_up", "we_down") if _drop_free(cfg)
            else ("we1", "we2"))


def init_transformer_params(cfg: TransformerConfig, mesh: Mesh, seed=0):
    """Initialize params laid out for the mesh; returns (params, specs).

    Layer stacks have shape (pp, layers_per_stage, ...) so the leading
    axis shards over pipeline stages.
    """
    _validate_config(cfg)
    pp = mesh.shape.get("pp", 1)
    assert not cfg.dense_layers or pp == 1, "dense_layers needs pp == 1"
    assert (cfg.n_layers - cfg.dense_layers) % pp == 0, \
        "n_layers must divide pp"
    kinds = kv_layer_kinds(cfg)
    n_linear = kinds.count("linear")
    n_own = dict((k, kinds.count(k)) for k in ("mamba", "gmu", "cross"))
    assert not (n_linear or any(n_own.values())) or pp == 1, \
        "linear_layout and mamba_layout need pp == 1"
    lps = (cfg.n_layers - cfg.dense_layers - n_linear
           - sum(n_own.values())) // pp
    rng = np.random.RandomState(seed)
    d, f, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd = _head_dim(cfg)
    dq = cfg.n_heads * hd
    dkv = _kv_heads(cfg) * hd
    s = 0.02

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape) * s, cfg.dtype)

    def gated(prefix, n, width, *lead):
        return {prefix + "gate": rand(pp, n, *lead, d, width),
                prefix + "up": rand(pp, n, *lead, d, width),
                prefix + "down": rand(pp, n, *lead, width, d)}

    def gain(*shape):
        """A norm's gain: ones, or drawn around 0 where it is (1 + w)."""
        return rand(*shape) if cfg.norm_zero_centered \
            else jnp.ones(shape, cfg.dtype)

    def norms(n):
        return {"ln1_g": gain(pp, n, d),
                "ln1_b": jnp.zeros((pp, n, d), cfg.dtype),
                "ln2_g": gain(pp, n, d),
                "ln2_b": jnp.zeros((pp, n, d), cfg.dtype)}

    def block(n):
        """Norms and attention maps of a stack of ``n`` layers."""
        out = norms(n)
        if _is_mla(cfg):
            rq, rkv, nh = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_heads
            dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            out.update({
                "wq_a": rand(pp, n, d, rq),
                "q_ln_g": jnp.ones((pp, n, rq), cfg.dtype),
                "wq_b": rand(pp, n, rq, nh * (dn + dr)),
                "wkv_a": rand(pp, n, d, rkv + dr),
                "kv_ln_g": jnp.ones((pp, n, rkv), cfg.dtype),
                "wkv_b": rand(pp, n, rkv, nh * (dn + dv)),
                "wo": rand(pp, n, nh * dv, d)})
        else:
            out.update({"wq": rand(pp, n, d,
                                   dq * (2 if cfg.attn_gate else 1)),
                        "wk": rand(pp, n, d, dkv),
                        "wv": rand(pp, n, d, dkv), "wo": rand(pp, n, dq, d)})
            if cfg.qk_norm:
                out.update({"q_norm_g": gain(pp, n, hd),
                            "k_norm_g": gain(pp, n, hd)})
            if cfg.attn_bias:
                out.update({"bq": rand(pp, n, dq), "bk": rand(pp, n, dkv),
                            "bv": rand(pp, n, dkv), "bo": rand(pp, n, d)})
            if cfg.diff_attn:
                # lambda vectors normal(0, 0.1), as the paper draws them
                out.update(dict((name, jnp.asarray(
                    rng.randn(pp, n, hd) * 0.1, cfg.dtype))
                    for name in _DIFF_PARAMS[:4]))
                out["subln_g"] = jnp.ones((pp, n, 2 * hd), cfg.dtype)
        return out

    def mamba_block(n):
        """Norms and Mamba maps of a stack of ``n`` layers, set as Mamba
        sets them: A_log = log(1 .. d_state) a channel, the step size's
        bias the inverse softplus of steps spread log-evenly over [1e-3,
        1e-1] across the channels, its map uniform within dt_rank **
        -0.5, D ones."""
        di, ns = cfg.mamba_expand * d, cfg.mamba_d_state
        r, taps = cfg.mamba_dt_rank, cfg.mamba_d_conv
        dt = np.broadcast_to(np.exp(np.linspace(np.log(1e-3), np.log(1e-1),
                                                di)), (pp, n, di))
        return dict(norms(n), **{
            "mb_in": rand(pp, n, d, 2 * di),
            "mb_conv": jnp.asarray(rng.randn(pp, n, taps, di) * taps ** -0.5,
                                   cfg.dtype),
            "mb_conv_b": rand(pp, n, di),
            "mb_x": rand(pp, n, di, r + 2 * ns),
            "mb_dt": jnp.asarray(rng.uniform(-1, 1, (pp, n, r, di))
                                 * r ** -0.5, cfg.dtype),
            "mb_dt_b": jnp.asarray(dt + np.log(-np.expm1(-dt)), cfg.dtype),
            "mb_a_log": jnp.asarray(np.broadcast_to(
                np.log(np.arange(1, ns + 1, dtype=np.float64)),
                (pp, n, di, ns)), cfg.dtype),
            "mb_d": jnp.ones((pp, n, di), cfg.dtype),
            "mb_out": rand(pp, n, di, d)})

    def linear_block(n):
        """Norms and Gated DeltaNet maps of a stack of ``n`` layers; the
        decays' A_log drawn wide (memories of a token to hundreds)."""
        dk_all = cfg.linear_key_heads * cfg.linear_key_dim
        dv_all = cfg.linear_value_heads * cfg.linear_value_dim
        vh = cfg.linear_value_heads
        return dict(norms(n), **{
            "gdn_qkvz": rand(pp, n, d, 2 * dk_all + 2 * dv_all),
            "gdn_ba": rand(pp, n, d, 2 * vh),
            "gdn_conv": jnp.asarray(
                rng.randn(pp, n, cfg.linear_conv_width,
                          2 * dk_all + dv_all) * 0.5, cfg.dtype),
            "gdn_a_log": jnp.asarray(rng.randn(pp, n, vh) * 2.0, cfg.dtype),
            "gdn_dt_bias": jnp.ones((pp, n, vh), cfg.dtype),
            "gdn_norm_g": jnp.ones((pp, n, cfg.linear_value_dim),
                                   cfg.dtype),
            "gdn_out": rand(pp, n, dv_all, d)})

    def ffn(n):
        """The block's second half for a stack of ``n`` layers."""
        if _drop_free(cfg):
            out = {"gate": rand(pp, n, d, cfg.num_experts)}
            out.update(gated("we_", n, f, _moe_held(cfg)))
            if cfg.moe_router == "noaux_tc":
                out["gate_bias"] = rand(pp, n, cfg.num_experts)
            if cfg.moe_shared_width:
                out.update(gated("ws_", n, cfg.moe_shared_width))
            if cfg.moe_shared_gate:
                out["ws_sigmoid"] = rand(pp, n, d, 1)
            return out
        if cfg.num_experts:
            return {"gate": rand(pp, n, d, cfg.num_experts),
                    "we1": rand(pp, n, cfg.num_experts, d, f),
                    "we2": rand(pp, n, cfg.num_experts, f, d)}
        if cfg.ffn_gated:
            return gated("w_", n, f)
        return {"w1": rand(pp, n, d, f), "w2": rand(pp, n, f, d)}

    layers = dict(block(lps), **ffn(lps))
    params = {
        "embed": rand(V, d),
        "lnf_g": gain(d),
        "lnf_b": jnp.zeros((d,), cfg.dtype),
        "layers": layers,
    }
    if cfg.dense_layers:
        params["dense_layers"] = dict(
            block(cfg.dense_layers),
            **gated("w_", cfg.dense_layers, cfg.d_ff_dense))
    if n_linear:
        params["linear_layers"] = dict(linear_block(n_linear),
                                       **ffn(n_linear))
    if n_own["mamba"]:
        params["mamba_layers"] = dict(mamba_block(n_own["mamba"]),
                                      **ffn(n_own["mamba"]))
    if n_own["gmu"]:
        di = cfg.mamba_expand * d
        params["gmu_layers"] = dict(
            norms(n_own["gmu"]), gmu_in=rand(pp, n_own["gmu"], d, di),
            gmu_out=rand(pp, n_own["gmu"], di, d), **ffn(n_own["gmu"]))
    if n_own["cross"]:
        params["cross_layers"] = dict(block(n_own["cross"]),
                                      **ffn(n_own["cross"]))
    if not cfg.tie_embeddings:
        params["head"] = rand(d, V)
    if cfg.pos_type == "learned":
        # rope has no length-bound table; don't allocate/shard/update one
        params["pos"] = rand(cfg.max_len, d)
    specs = _param_specs(cfg, pp)
    # an RMSNorm has no shift: drop what the specs do not name
    params = {k: v for k, v in params.items() if k in specs}
    for stack in _STACKS:
        if stack in params:
            params[stack] = {k: v for k, v in params[stack].items()
                             if k in specs[stack]}
    shard = {k: (jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp),
                                        specs[k])
                 if isinstance(specs[k], dict) else
                 NamedSharding(mesh, specs[k])) for k in specs}
    params = jax.tree_util.tree_map(
        lambda x, sh: jax.device_put(x, sh), params, shard)
    return params, specs


# ---------------------------------------------------------------------------
# local (per-device) model
# ---------------------------------------------------------------------------

def _pvary(x, axes):
    """pcast to varying only over axes x is not already varying on
    (pcast rejects varying->varying)."""
    cur = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in cur)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _norm(cfg, p, name, x):
    """The block's normalisation over the last axis with parameters
    ``p[name + "_g"]`` (and ``"_b"``): LayerNorm in the array's own
    type, or RMSNorm — x / sqrt(mean(x^2) + eps) * g, the mean taken in
    float32 whatever the array's type."""
    if cfg.norm == "rmsnorm":
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        xn = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
        if cfg.norm_zero_centered:      # the gain is (1 + w), in float32
            return (xn * (1.0 + p[name + "_g"].astype(jnp.float32))) \
                .astype(x.dtype)
        return xn.astype(x.dtype) * p[name + "_g"]
    return _ln(x, p[name + "_g"], p[name + "_b"], cfg.norm_eps)


def _logits(cfg, params, x):
    """Output map: the embedding's transpose, or the model's own."""
    x = _norm(cfg, params, "lnf", x)
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["head"]


def _ffn(cfg, lp, h, x_in, layers, at):
    """The block's second half on ONE device: ``h`` the normalised
    residual stream (..., d), ``x_in`` the stream as it entered the
    layer (what an early router reads); ``layers[name][at]`` is
    ``lp[name]`` (the grouped expert product reads the stacks in place:
    a sliced operand of a custom call is a copy). Returns ``(f,
    stats)``; stats is None for a dense FFN, else ``(experts, active)``
    of the drop-free router (None, None for the capacity router, which
    reports none)."""
    if "w_gate" in lp:          # a layer of the leading dense stack
        return _gated_ffn(cfg, lp, "w_", h), None
    if not cfg.num_experts:
        return jax.nn.gelu(h @ lp["w1"]) @ lp["w2"], None
    d = h.shape[-1]
    tok = h.reshape(-1, d)
    if _drop_free(cfg):
        src = x_in if cfg.moe_router_input == "layer" else h
        # the logits decide by their order: accumulate in float32
        logits = jnp.dot(src.reshape(-1, d), lp["gate"],
                         preferred_element_type=jnp.float32)
        route = None
        if cfg.moe_router == "noaux_tc":
            route = functools.partial(
                group_limited_routing, bias=lp["gate_bias"],
                k=cfg.moe_top_k, n_groups=cfg.moe_n_groups,
                topk_groups=cfg.moe_topk_groups,
                scale=cfg.moe_routed_scale)
        out, experts, active = moe_ffn_sorted(
            tok, logits, layers["we_gate"], layers["we_up"],
            layers["we_down"], cfg.moe_top_k, lead=at, route=route,
            first=_moe_first(cfg), act=cfg.gate_act)
        if cfg.moe_shared_width:
            shared = _gated_ffn(cfg, lp, "ws_", tok)
            if cfg.moe_shared_gate:
                shared = shared * jax.nn.sigmoid(jnp.dot(
                    tok, lp["ws_sigmoid"],
                    preferred_element_type=jnp.float32)).astype(tok.dtype)
            out = out + shared
        return out.reshape(h.shape), (experts, active)
    logits = tok @ lp["gate"]
    cap = max(1, int(cfg.capacity_factor * tok.shape[0]
                     * min(cfg.moe_top_k, 2) / cfg.num_experts))
    disp, comb, _ = top_k_gating(logits, cfg.num_experts, cap,
                                 k=cfg.moe_top_k)
    exp_in = jnp.einsum("nec,nd->ecd", disp.astype(h.dtype), tok)
    hh = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", exp_in, lp["we1"]))
    eo = jnp.einsum("ecf,efd->ecd", hh, lp["we2"])
    f = jnp.einsum("nec,ecd->nd", comb.astype(h.dtype), eo)
    return f.reshape(h.shape), (None, None)


def _gated_ffn(cfg, lp, prefix, h):
    """``(act(h Wg) * (h Wu)) Wd`` with the maps ``lp[prefix + "gate" |
    "up" | "down"]``: a dense gated FFN, or a shared expert."""
    act = jax.nn.silu if cfg.gate_act == "silu" else jax.nn.relu
    return (act(h @ lp[prefix + "gate"]) * (h @ lp[prefix + "up"])) \
        @ lp[prefix + "down"]


def _iter_layers(params, cfg):
    """The model's layers in order: ``(flat index, the stack that holds
    the layer, its (stage, layer) there, its parameters)``. A model with
    two kinds of FFN keeps its leading dense layers in a stack of their
    own, ``params["dense_layers"]``, before ``params["layers"]``; one
    with linear-attention layers keeps those in ``params["linear_layers"]
    ``, among the others as ``linear_layout`` says; a decoder-hybrid-
    decoder its Mamba, gated-memory and cross-attention layers in
    ``params["mamba_layers"]``, ``["gmu_layers"]`` and
    ``["cross_layers"]`` (:func:`_layer_rule`)."""
    taken = dict.fromkeys(_STACKS, 0)
    for li_flat in range(cfg.n_layers):
        name = ("dense_layers" if li_flat < cfg.dense_layers
                else _STACK_OF.get(_layer_rule(cfg, li_flat)[0], "layers"))
        stack = params[name]
        lps = jax.tree_util.tree_leaves(stack)[0].shape[1]
        st, li = divmod(taken[name], lps)
        taken[name] += 1
        yield (li_flat, stack, (st, li), jax.tree_util.tree_map(
            lambda p: p[st, li], stack))


def _stats(cfg, per_layer):
    """What a forward reports beside its logits: for the drop-free
    routers, per expert layer the chosen experts (L, k, n) — choice-
    major, as ``moe_ffn_sorted`` returns them — and the number of held
    experts that received a row (L,); else nothing."""
    if not _drop_free(cfg):
        return {}
    per_layer = [p for p in per_layer if p is not None]
    return {"moe_experts": jnp.stack([e for e, _a in per_layer]),
            "moe_active_experts": jnp.stack(
                [a for _e, a in per_layer]).astype(jnp.int32)}


def _attention_local(lp, x, cfg, heads_local):
    """x: (B_l, S_l, d) -> (B_l, S_l, d) partial over tp (pre-psum).
    With GQA the K/V projections carry n_kv_heads/tp local heads,
    expanded to the query head count before the attention kernel.

    Note: expansion happens before the sp exchange, so ring/Ulysses
    move the EXPANDED tensors — correct, but GQA's ICI saving
    (rotating grouped K/V and expanding per chunk) is left on the
    table; revisit if sp-sharded GQA training becomes a hot path."""
    b, s, d = x.shape
    hd = d // cfg.n_heads
    kv_local = heads_local * _kv_heads(cfg) // cfg.n_heads
    q = x @ lp["wq"]                                      # (b, s, d_tp)
    k = x @ lp["wk"]
    v = x @ lp["wv"]

    def split(t, nh=heads_local):
        return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

    def split_kv(t):
        return _expand_kv(split(t, kv_local), heads_local // kv_local, 1)

    qh, kh, vh = split(q), split_kv(k), split_kv(v)
    if cfg.pos_type == "rope":
        # absolute positions of this sequence shard (ring/Ulysses move
        # K/V AFTER projection, so rotating here is globally correct)
        pos = jax.lax.axis_index("sp") * s + jnp.arange(s)
        qh = _rope(qh, pos, cfg.rope_base)
        kh = _rope(kh, pos, cfg.rope_base)

    if cfg.sp_attn == "ulysses":
        from .ulysses import _ulysses_local
        o = _ulysses_local(qh, kh, vh, "sp",
                           causal=True, sm_scale=1.0 / np.sqrt(hd),
                           impl="auto", interpret=None)
    else:
        o = _ring_attention_local(qh, kh, vh, "sp",
                                  causal=True, sm_scale=1.0 / np.sqrt(hd))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, heads_local * hd)
    return o @ lp["wo"]                                   # partial (b, s, d)


def _dense_ffn_local(lp, x):
    u = jax.nn.gelu(x @ lp["w1"])                         # (b, s, f_tp)
    return u @ lp["w2"]                                   # partial (b, s, d)


def _moe_ffn_local(lp, x, cfg, ep_size):
    """Local-token MoE: route this shard's tokens over the global expert
    set. Expert weights arrive ALREADY ep-sharded by shard_map in_specs
    ((E/ep, d, f) locally); dispatch/combine are computed over the full
    expert set and sliced to the local experts, outputs psum over ep."""
    b, s, d = x.shape
    tok = x.reshape(b * s, d)
    logits = tok @ lp["gate"]
    cap = max(1, int(cfg.capacity_factor * tok.shape[0]
                     * min(cfg.moe_top_k, 2) / cfg.num_experts))
    disp, comb, aux = top_k_gating(logits, cfg.num_experts, cap,
                                   k=cfg.moe_top_k)
    e_loc = cfg.num_experts // ep_size
    ei = jax.lax.axis_index("ep")
    d_loc = jax.lax.dynamic_slice_in_dim(disp, ei * e_loc, e_loc, axis=1)
    c_loc = jax.lax.dynamic_slice_in_dim(comb, ei * e_loc, e_loc, axis=1)
    exp_in = jnp.einsum("nec,nd->ecd", d_loc.astype(x.dtype), tok)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", exp_in, lp["we1"]))
    exp_out = jnp.einsum("ecf,efd->ecd", h, lp["we2"])
    out = jnp.einsum("nec,ecd->nd", c_loc.astype(x.dtype), exp_out)
    out = jax.lax.psum(out, "ep")
    return out.reshape(b, s, d), aux


def _block_local(lp, x, cfg, heads_local, ep_size):
    """One transformer block on local shards. Returns (x, aux_loss)."""
    a = _attention_local(lp, _ln(x, lp["ln1_g"], lp["ln1_b"]),
                         cfg, heads_local)
    x = x + jax.lax.psum(a, "tp")
    h = _ln(x, lp["ln2_g"], lp["ln2_b"])
    if cfg.num_experts:
        f, aux = _moe_ffn_local(lp, h, cfg, ep_size)
        # MoE experts are ep-sharded (not tp); both branches leave x
        # replicated over tp.
        return x + f, aux
    f = _dense_ffn_local(lp, h)
    return x + jax.lax.psum(f, "tp"), jnp.zeros((), x.dtype)


def _stage_local(stage_params, x, cfg, heads_local, ep_size):
    """Apply this pipeline stage's layers_per_stage blocks (scan over the
    layer axis). stage_params leaves: (lps, ...).

    The carry is pcast to varying over pp/ep up front: stage params are
    pp-sharded (and experts ep-sharded), so the scan output is varying
    over those axes — VMA requires the carry types to match."""
    x = _pvary(x, ("pp",))
    aux0 = _pvary(jnp.zeros((), x.dtype), ("dp", "sp", "pp"))

    block = _block_local
    if cfg.remat:
        # rematerialize each block on the backward pass: activation
        # memory drops from O(layers * s_local * d) to O(s_local * d)
        # per stage at ~1/3 extra FLOPs — the TPU long-context trade
        # (HBM is the bottleneck, MXU FLOPs are cheap)
        block = jax.checkpoint(
            _block_local, static_argnums=(2, 3, 4),
            policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, lp):
        x, aux = carry
        x, a = block(lp, x, cfg, heads_local, ep_size)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, aux0), stage_params)
    return x, aux


def _pipeline_stages_local(layers, x, cfg, heads_local, pp_size, ep_size,
                           num_microbatches):
    """GPipe schedule across the pp axis (see parallel/pipeline.py for
    the standalone version). x: (B_l, S_l, d). Activation shapes are
    constant across stages so the handoff is a single ppermute."""
    if pp_size == 1:
        x, aux = _stage_local(
            jax.tree_util.tree_map(lambda p: p[0], layers),
            x, cfg, heads_local, ep_size)
        # size-1 psum: numerically identity, collapses the pp-varying
        # type back to invariant so the loss can be replicated.
        return jax.lax.psum(x, "pp"), jax.lax.psum(aux, "pp")
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, "local batch %d vs microbatches %d" % (B, M)
    mb = B // M
    x_mb = x.reshape((M, mb) + x.shape[1:])
    stage = jax.tree_util.tree_map(lambda p: p[0], layers)
    idx = jax.lax.axis_index("pp")
    S = pp_size
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    is_first, is_last = idx == 0, idx == S - 1

    def tick(carry, t):
        state, out_buf, aux = carry
        feed = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        inp = jnp.where(is_first, feed, state)
        out, a = _stage_local(stage, inp, cfg, heads_local, ep_size)
        mb_done = t - (S - 1)
        valid = jnp.logical_and(is_last, mb_done >= 0)
        onehot = (jnp.arange(M) == mb_done).astype(out.dtype)
        upd = onehot.reshape((M, 1, 1, 1)) * out[None]
        out_buf = out_buf + jnp.where(valid, upd, jnp.zeros_like(upd))
        # this stage holds real data only for ticks in [idx, idx + M):
        # bubble ticks must not pollute the MoE aux loss
        live = jnp.logical_and(t >= idx, t < idx + M).astype(a.dtype)
        state = jax.lax.ppermute(out, "pp", perm)
        return (state, out_buf, aux + a * live), None

    st0 = _pvary(jnp.zeros_like(x_mb[0]), ("pp",))
    buf0 = _pvary(jnp.zeros_like(x_mb), ("pp",))
    aux0 = _pvary(jnp.zeros((), x.dtype), ("dp", "sp", "pp"))
    (_, out_buf, aux), _ = jax.lax.scan(
        tick, (st0, buf0, aux0), jnp.arange(T))
    out = jax.lax.psum(out_buf, "pp")           # only last stage non-zero
    aux = jax.lax.psum(aux, "pp")               # sum stage contributions
    return out.reshape((B,) + x.shape[1:]), aux


def _lm_local_loss(params, tokens, targets, cfg, mesh_shape,
                   num_microbatches):
    """Per-device loss over local (dp, sp) shards of tokens/targets."""
    tp, pp, ep = mesh_shape["tp"], mesh_shape["pp"], mesh_shape["ep"]
    heads_local = cfg.n_heads // tp
    b, s_loc = tokens.shape
    sp_i = jax.lax.axis_index("sp")
    pos0 = sp_i * s_loc

    x = params["embed"][tokens]                       # (b, s_loc, d)
    if cfg.pos_type == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos"], pos0,
                                             s_loc, 0)

    # tp shard the head/ffn dims of the layer stacks locally: shard_map
    # already sliced them via in_specs; layers leaves arrive local.
    x, aux = _pipeline_stages_local(params["layers"], x, cfg, heads_local,
                                    pp, ep, num_microbatches)
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["embed"].T                    # (b, s_loc, V)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    local_sum = jnp.sum(nll)
    total = jax.lax.psum(local_sum, ("dp", "sp"))
    count = jax.lax.psum(jnp.asarray(nll.size, jnp.float32), ("dp", "sp"))
    return total / count + 0.01 * jax.lax.psum(aux, ("dp", "sp")) / (
        mesh_shape["dp"] * mesh_shape["sp"])


def make_transformer_train_step(cfg: TransformerConfig, mesh: Mesh,
                                lr=0.1, num_microbatches=None,
                                device_loop=False):
    """Build ``step(params, tokens, targets) -> (params, loss)`` — one
    compiled SPMD program doing forward, backward, psum, SGD.

    The shard_map wraps the LOSS only, with replication checking ON, so
    JAX's manual-SPMD AD inserts the correct psum/pbroadcast transposes
    for every mix of sharded (tp/pp/ep) and replicated parameters —
    gradients need no hand reductions. value_and_grad + the SGD update
    sit outside and fuse into the same XLA program under jit.

    mesh must carry all of ``("dp","sp","tp","pp","ep")`` (size 1 ok).
    tokens/targets: (batch, seq) int32, sharded (dp, sp).

    ``device_loop=True`` returns ``loop(params, tokens, targets)`` over
    STACKED (k, batch, seq) batches instead: k steps scanned on device
    in one compiled program (one dispatch per k steps).
    """
    for ax in AXES:
        if ax not in mesh.axis_names:
            raise ValueError("mesh is missing axis %r" % ax)
    mesh_shape = {a: mesh.shape[a] for a in AXES}
    _validate_config(cfg)
    _validate_trainable(cfg)
    if _kv_heads(cfg) % mesh_shape["tp"]:
        raise ValueError(
            "GQA: n_kv_heads=%d must divide by tp=%d (K/V projections "
            "are tp-sharded on the head dim)"
            % (_kv_heads(cfg), mesh_shape["tp"]))
    if cfg.sp_attn == "ulysses":
        heads_local = cfg.n_heads // mesh_shape["tp"]
        if heads_local % mesh_shape["sp"]:
            raise ValueError(
                "sp_attn='ulysses': local heads %d (n_heads=%d / tp=%d) "
                "not divisible by sp=%d — use sp_attn='ring' for "
                "few-head layouts" % (heads_local, cfg.n_heads,
                                      mesh_shape["tp"], mesh_shape["sp"]))
    M = num_microbatches or max(1, mesh_shape["pp"])
    specs = _param_specs(cfg, mesh_shape["pp"])

    pspec = {k: (v if not isinstance(v, dict) else dict(v))
             for k, v in specs.items()}
    data_spec = P("dp", "sp")
    loss_fn = shard_map(
        functools.partial(_lm_local_loss, cfg=cfg, mesh_shape=mesh_shape,
                          num_microbatches=M),
        mesh=mesh, in_specs=(pspec, data_spec, data_spec), out_specs=P())

    def step(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    if not device_loop:
        return jax.jit(step, donate_argnums=(0,))

    def loop(params, tokens, targets):
        """``k`` steps as one program: scan over stacked (k, b, s)
        batches — one dispatch per k steps (the reference's engine
        bulking, done the TPU way). Returns (params, last_loss)."""
        def body(p, xs):
            tok, tgt = xs
            p, loss = step(p, tok, tgt)
            return p, loss

        params, losses = jax.lax.scan(body, params, (tokens, targets))
        return params, losses[-1]

    return jax.jit(loop, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# latent attention (MLA): one function a mechanism, shared by the whole-
# sequence forward, the prefill and the decode step
# ---------------------------------------------------------------------------

def _rope_rows(t, pos, inv_freq):
    """t (..., heads, hd), pos (...) or broadcastable to it: each row's
    heads rotate by the row's position, pairs (i, i + hd/2)."""
    half = t.shape[-1] // 2
    ang = jnp.asarray(pos)[..., None, None].astype(jnp.float32) \
        * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang).astype(t.dtype), jnp.sin(ang).astype(t.dtype)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def _mla_scale(cfg):
    """The softmax scale: (nope + rope) ** -0.5, times YaRN's mscale²."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * _yarn_mscale(cfg) ** 2


def _mla_compress(cfg, lp, h, pos):
    """The layer's two down-projections of its normalised input ``h``
    (..., d) at positions ``pos`` (...): ``(c_q, latent)`` — the
    normalised compressed query (..., q_lora_rank) and what the cache
    holds of the token, ``[RMSNorm(c_KV) ; rotated k_r]`` (...,
    kv_lora_rank + rope)."""
    r = cfg.kv_lora_rank
    c_q = _norm(cfg, lp, "q_ln", h @ lp["wq_a"])
    down = h @ lp["wkv_a"]
    k_r = _rope_rows(down[..., None, r:], pos,
                     _rope_inv_freq(cfg, cfg.qk_rope_head_dim))[..., 0, :]
    return c_q, jnp.concatenate(
        [_norm(cfg, lp, "kv_ln", down[..., :r]), k_r], -1)


def _mla_queries(cfg, wq_b, c_q, pos):
    """``(q_nope (..., heads, nope), q_rope (..., heads, rope))`` of the
    heads whose columns ``wq_b`` holds; q_rope rotated by ``pos``."""
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (c_q @ wq_b).reshape(c_q.shape[:-1] + (-1, dn + dr))
    return q[..., :dn], _rope_rows(q[..., dn:], pos,
                                   _rope_inv_freq(cfg, dr))


# head-rows (heads x positions) one decompressed prefill attention holds
# at a time: a longer prompt's heads go through in groups, one after the
# other, so that its per-head queries, keys, values and outputs (five
# arrays of 128 heads x 16384 positions are 2.4 GB in bf16) stay a
# quarter of that
_MLA_PREFILL_HEAD_ROWS = 128 * 4096


def _mla_attend_prompt(cfg, lp, c_q, latent):
    """The DECOMPRESSED attend over whole sequences: c_q (b, s, q_rank),
    latent (b, s, kv_rank + rope) -> the layer's attention output (b, s,
    d). Per head, keys ``[c_KV W_UK ; k_r]`` against values ``c_KV
    W_UV``, causal (``ops/pallas/mla_attention.mla_flash_prefill``; its
    lax twin off the TPU)."""
    from ..ops.pallas.mla_attention import mla_flash_prefill
    b, s, _ = c_q.shape
    r, nh = cfg.kv_lora_rank, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_kv, k_r = latent[..., :r], latent[..., r:]
    groups = 1
    while nh % (2 * groups) == 0 \
            and nh // groups * s > _MLA_PREFILL_HEAD_ROWS:
        groups *= 2
    hg = nh // groups
    wq_b = lp["wq_b"].reshape(-1, groups, hg * (dn + dr))
    wkv_b = lp["wkv_b"].reshape(r, groups, hg, dn + dv)
    pos = jnp.arange(s)[None, :]

    def attend(g):
        q_nope, q_rope = _mla_queries(cfg, wq_b[:, g], c_q, pos)
        w = wkv_b[:, g]                                  # (r, hg, dn + dv)
        return mla_flash_prefill(
            q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3),
            jnp.einsum("bsr,rhn->bhsn", c_kv, w[..., :dn]), k_r,
            jnp.einsum("bsr,rhv->bhsv", c_kv, w[..., dn:]),
            _mla_scale(cfg))                             # (b, hg, s, dv)

    if groups == 1:
        o = attend(0)[None]
    else:
        o = jax.lax.map(attend, jnp.arange(groups))      # (G, b, hg, s, dv)
    return jnp.einsum("gbhsv,ghvd->bsd", o,
                      lp["wo"].reshape(groups, hg, dv, -1))


def _mla_attend_latent(cfg, lp, c_q, cache, li, pos_b):
    """The ABSORBED attend of one token a row over layer ``li`` of a
    :class:`LatentKVCache`: c_q (b, q_rank) at positions ``pos_b`` ->
    (b, d). ``W_UK`` is folded into the query and ``W_UV`` applied to
    the attended latent, so nothing is decompressed per cached token —
    the same mathematics as :func:`_mla_attend_prompt`."""
    from ..ops.pallas.mla_attention import mla_paged_decode
    r, nh = cfg.kv_lora_rank, cfg.n_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_queries(cfg, lp["wq_b"], c_q, pos_b)
    w = lp["wkv_b"].reshape(r, nh, dn + dv)
    q = jnp.concatenate(
        [jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :dn]), q_rope], -1)
    o_lat = mla_paged_decode(q, cache.pages, cache.block_tables, pos_b + 1,
                             _mla_scale(cfg), r, layer=li)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., dn:])
    return o.reshape(o.shape[0], nh * dv) @ lp["wo"]


def _latent_write_token(cache, li, latent_t, pos_b):
    """One token's latent (b, width) into its row's page at ``pos_b``:
    the lane tile (width, lt) that holds the position is read, one lane
    replaced, and written back whole — its indices (layer, page, tile)
    are all untiled dims, so the pool stays in place."""
    from ..ops.pallas.mla_attention import lane_tile
    ps = cache.page_size
    lt = lane_tile(ps)
    page = jnp.take_along_axis(
        cache.block_tables, (pos_b // ps)[:, None], axis=1)[:, 0]
    tile, lane = pos_b % ps // lt, pos_b % lt
    new = jnp.where(
        (jnp.arange(lt)[None, :] == lane[:, None])[:, None, :],
        latent_t[:, :, None].astype(cache.pages.dtype),
        cache.pages[li, page, tile])                     # (b, width, lt)
    return LatentKVCache(cache.pages.at[li, page, tile].set(new),
                         cache.block_tables, cache.page_size)


def _latent_write_prompt(cache, li, latent, lengths):
    """A prompt's latents (b, s, width) into its rows' pages: the pages
    that hold a real position (by ``lengths``; the padded tail goes to
    the null page 0), so a row needs pages for its tokens, not for its
    bucket."""
    from ..ops.pallas.flash_attention import prefill_page_dest
    from ..ops.pallas.mla_attention import pages_of_latents
    ps = cache.page_size
    s = latent.shape[1]
    if s % ps:
        raise ValueError("prefill bucket %d is not a multiple of "
                         "page_size %d" % (s, ps))
    dest = prefill_page_dest(cache.block_tables, s // ps, ps, lengths)
    return LatentKVCache(
        cache.pages.at[li, dest].set(
            pages_of_latents(latent, ps).astype(cache.pages.dtype)),
        cache.block_tables, cache.page_size)


# ---------------------------------------------------------------------------
# the per-head K/V attention's projections, and linear attention (Gated
# DeltaNet): one function a mechanism, shared by the whole-sequence
# forward, the prefill and the decode step
# ---------------------------------------------------------------------------

def _rotate(cfg, t, pos):
    """Rotary embedding of t (..., heads, hd) at positions ``pos`` (...):
    the pairs (i, i + rot / 2) of the first ``rot = head_dim *
    rotary_share`` dimensions turn (:func:`_rope_rows`), the rest pass."""
    rot = int(t.shape[-1] * cfg.rotary_share)
    half = rot // 2
    freqs = cfg.rope_base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    turned = _rope_rows(t[..., :rot], pos, freqs)
    if rot == t.shape[-1]:
        return turned
    return jnp.concatenate([turned, t[..., rot:]], -1)


def _attn_qkv(cfg, lp, h, pos, rotary):
    """A full or window layer's ``(q (..., heads, hd), k, v (..., kv
    heads, hd), gate (..., heads * hd) or None)`` from its normalised
    input ``h`` (..., d) at positions ``pos`` (...): the three maps; with
    ``attn_gate`` a head's columns of ``wq`` are [query ; gate]; with
    ``qk_norm`` an RMSNorm over every head of q and of k; the rotation
    where the layer has one (:func:`_rotate`)."""
    hd = _head_dim(cfg)
    lead = h.shape[:-1]
    q = (h @ lp["wq"]).reshape(lead + (cfg.n_heads, -1))
    gate = None
    if cfg.attn_gate:
        q, gate = q[..., :hd], q[..., hd:].reshape(lead + (-1,))
    k = (h @ lp["wk"]).reshape(lead + (_kv_heads(cfg), hd))
    v = (h @ lp["wv"]).reshape(lead + (_kv_heads(cfg), hd))
    if cfg.qk_norm:
        q, k = _norm(cfg, lp, "q_norm", q), _norm(cfg, lp, "k_norm", k)
    if rotary:
        q, k = _rotate(cfg, q, pos), _rotate(cfg, k, pos)
    return q, k, v, gate


def _attn_out(lp, o, gate):
    """The attention's output map over o (..., heads * hd), times the
    sigmoid of the layer's gate where it has one."""
    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    return o @ lp["wo"]


def _gdn_inputs(cfg, lp, h, tail=None, lengths=None):
    """A Gated DeltaNet layer's projections, convolution and gates over
    its normalised input ``h`` (b, s, d). ``tail`` (b, width - 1,
    channels): the inputs of the convolution before this call's first
    position (None: the sequence starts here, zeros); ``lengths`` (b,):
    the rows' real lengths (None: s). Returns ``(q, k (b, s, key heads,
    dk), v, z (b, s, value heads, dv), g, beta (b, s, value heads)
    float32, tail')``: q and k after the causal depthwise convolution and
    SiLU, L2-normalised a head (q times dk ** -0.5 too); ``g = -exp(A_log)
    softplus(a + dt_bias)`` the log of the state's decay and ``beta =
    sigmoid(b)`` the write strength, both 0 at and past a row's length so
    that the padding leaves the state as it is; ``tail'`` the last
    ``width - 1`` inputs before the row's length (zeros on the left of a
    row shorter than that)."""
    b, s, _ = h.shape
    kh, vh = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    taps = cfg.linear_conv_width
    f32 = jnp.float32
    qkvz = h @ lp["gdn_qkvz"]
    ba = jnp.dot(h, lp["gdn_ba"], preferred_element_type=f32)
    n_conv = 2 * kh * dk + vh * dv
    x, z = qkvz[..., :n_conv], qkvz[..., n_conv:]
    if tail is None:
        tail = jnp.zeros((b, taps - 1, n_conv), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = lp["gdn_conv"].astype(f32)
    y = sum(xp[:, j:j + s].astype(f32) * w[j] for j in range(taps))
    y = jax.nn.silu(y).astype(h.dtype).astype(f32)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    # x position p sits at xp[p + taps - 1]: the tail ends at lengths - 1
    new_tail = jnp.take_along_axis(
        xp, (lengths[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)

    def unit(t):                      # L2 norm over a head, eps 1e-6
        return t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(y[..., :kh * dk].reshape(b, s, kh, dk)) * dk ** -0.5
    k = unit(y[..., kh * dk:2 * kh * dk].reshape(b, s, kh, dk))
    v = y[..., 2 * kh * dk:].reshape(b, s, vh, dv).astype(h.dtype)
    real = (jnp.arange(s)[None, :] < lengths[:, None])[:, :, None]
    g = -jnp.exp(lp["gdn_a_log"].astype(f32)) * jax.nn.softplus(
        ba[..., vh:] + lp["gdn_dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(ba[..., :vh])
    return (q, k, v, z.reshape(b, s, vh, dv), jnp.where(real, g, 0.0),
            jnp.where(real, beta, 0.0), new_tail)


def _gdn_out(cfg, lp, o, z):
    """The layer's output from the rule's read-out ``o`` and the gate
    ``z`` (..., value heads, dv): RMSNorm over a head with a plain gain
    (not zero-centred), times SiLU(z) in float32, then the output map."""
    of = o.astype(jnp.float32)
    on = (of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True)
                             + cfg.norm_eps)).astype(z.dtype) \
        * lp["gdn_norm_g"]
    gated = (on.astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return gated.reshape(gated.shape[:-2] + (-1,)) @ lp["gdn_out"]


def _gdn_prompt(cfg, lp, h, lengths=None):
    """A Gated DeltaNet layer over whole sequences from their start: h
    (b, s, d) -> ``(out (b, s, d), state (b, value heads, dk, dv) float32,
    tail)`` — the state and the convolution's tail as they stand after
    each row's ``lengths`` (the chunked rule,
    ``ops/pallas/gated_delta.gdn_chunk_prefill``)."""
    from ..ops.pallas.gated_delta import gdn_chunk_prefill
    q, k, v, z, g, beta, tail = _gdn_inputs(cfg, lp, h, None, lengths)
    o, state = gdn_chunk_prefill(q, k, v, g, beta)
    return _gdn_out(cfg, lp, o, z), state, tail


def _gdn_token(cfg, lp, h, cache, li):
    """One token a row through linear layer ``li`` (its index among the
    linear layers) of a :class:`LinearStateCache`: h (b, d) -> ``(out (b,
    d), cache)``. Each row's state row is read, decayed, written to by
    the delta rule and read out, in place in the pool (the recurrent
    rule, ``ops/pallas/gated_delta.gdn_recurrent_step``); its
    convolution tail takes the token."""
    from ..ops.pallas.gated_delta import gdn_recurrent_step
    b = h.shape[0]
    rows = cache.rows
    tail = cache.conv[li, rows].reshape(b, cfg.linear_conv_width - 1, -1)
    q, k, v, z, g, beta, tail = _gdn_inputs(cfg, lp, h[:, None], tail)
    o, state = gdn_recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], cache.state, rows, layer=li)
    conv = cache.conv.at[li, rows].set(
        tail.reshape(b, -1).astype(cache.conv.dtype))
    return (_gdn_out(cfg, lp, o.astype(h.dtype), z[:, 0]),
            LinearStateCache(cache.full, state, conv, rows))


# ---------------------------------------------------------------------------
# the decoder-hybrid-decoder's mechanisms (SambaY): the Mamba layer, the
# gated memory unit, differential attention — one function a mechanism,
# shared by the whole-sequence forward, the prefill and the decode step
# ---------------------------------------------------------------------------

def _mamba_inputs(cfg, lp, h, tail=None, lengths=None):
    """A Mamba layer's input map, causal depthwise convolution and step
    sizes over its normalised input ``h`` (b, s, d). ``tail`` (b, d_conv
    - 1, channels): the convolution's inputs before this call's first
    position (None: the sequence starts here, zeros); ``lengths`` (b,):
    the rows' real lengths (None: s). Returns ``(x (b, s, channels)
    after the convolution's bias and SiLU, z (b, s, channels), delta (b,
    s, channels) float32, B, C (b, s, d_state) float32, tail')``: ``delta
    = softplus(dt_proj(dt) + b_dt)``, 0 at and past a row's length so
    that the padding leaves the state as it is; ``tail'`` the last
    ``d_conv - 1`` inputs before the row's length."""
    b, s, _ = h.shape
    di = cfg.mamba_expand * cfg.d_model
    ns, r, taps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    f32 = jnp.float32
    xz = h @ lp["mb_in"]
    x, z = xz[..., :di], xz[..., di:]
    if tail is None:
        tail = jnp.zeros((b, taps - 1, di), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = lp["mb_conv"].astype(f32)
    y = sum(xp[:, j:j + s].astype(f32) * w[j] for j in range(taps))
    x = jax.nn.silu(y + lp["mb_conv_b"].astype(f32)).astype(h.dtype)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    # x position p sits at xp[p + taps - 1]: the tail ends at lengths - 1
    new_tail = jnp.take_along_axis(
        xp, (lengths[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    dbc = jnp.dot(x, lp["mb_x"], preferred_element_type=f32)
    dt = jnp.dot(dbc[..., :r].astype(h.dtype), lp["mb_dt"],
                 preferred_element_type=f32) + lp["mb_dt_b"].astype(f32)
    real = (jnp.arange(s)[None, :] < lengths[:, None])[:, :, None]
    delta = jnp.where(real, jax.nn.softplus(dt), 0.0)
    return (x, z, delta, dbc[..., r:r + ns], dbc[..., r + ns:], new_tail)


def _mamba_a(lp):
    """``A = -exp(A_log)`` as the scan takes it: (d_state, channels)."""
    return -jnp.exp(lp["mb_a_log"].astype(jnp.float32)).T


def _mamba_out(lp, y, z):
    """``(out, m)``: the scan's read-out gated, ``m = y * silu(z)`` (...,
    channels) — what a gated memory unit reads of the layer — and the
    output map of it."""
    m = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))) \
        .astype(z.dtype)
    return m @ lp["mb_out"], m


def _mamba_prompt(cfg, lp, h, lengths=None):
    """A Mamba layer over whole sequences from their start: h (b, s, d) ->
    ``(out (b, s, d), m (b, s, channels), state (b, d_state, channels)
    float32, tail)`` — the state and the convolution's tail as they stand
    after each row's ``lengths`` (the chunked scan,
    ``ops/pallas/mamba.mamba_chunk_scan``)."""
    from ..ops.pallas.mamba import mamba_chunk_scan
    x, z, delta, bm, cm, tail = _mamba_inputs(cfg, lp, h, None, lengths)
    y, state = mamba_chunk_scan(x, delta, bm, cm, _mamba_a(lp), lp["mb_d"])
    out, m = _mamba_out(lp, y, z)
    return out, m, state, tail


def _mamba_token(cfg, lp, h, cache, li):
    """One token a row through Mamba layer ``li`` (its index among the
    Mamba layers) of a :class:`LinearStateCache`: h (b, d) -> ``(out (b,
    d), m (b, channels), cache)``. Each row's state row is decayed,
    written and read out in place in the pool (the recurrent rule,
    ``ops/pallas/mamba.mamba_recurrent_step``); its convolution tail
    takes the token."""
    from ..ops.pallas.mamba import mamba_recurrent_step
    b = h.shape[0]
    rows = cache.rows
    tail = cache.conv[li, rows].reshape(b, cfg.mamba_d_conv - 1, -1)
    x, z, delta, bm, cm, tail = _mamba_inputs(cfg, lp, h[:, None], tail)
    y, state = mamba_recurrent_step(x[:, 0], delta[:, 0], bm[:, 0],
                                    cm[:, 0], _mamba_a(lp), lp["mb_d"],
                                    cache.state, rows, layer=li)
    conv = cache.conv.at[li, rows].set(
        tail.reshape(b, -1).astype(cache.conv.dtype))
    out, m = _mamba_out(lp, y, z[:, 0])
    return out, m, LinearStateCache(cache.full, state, conv, rows)


def _gmu(lp, h, m):
    """A gated memory unit: ``(m * silu(h W_in)) W_out`` over the
    normalised input ``h`` and the memory ``m`` (..., channels)."""
    gate = jax.nn.silu(jnp.dot(h, lp["gmu_in"],
                               preferred_element_type=jnp.float32))
    return (m.astype(jnp.float32) * gate).astype(h.dtype) @ lp["gmu_out"]


def _diff_qkv(cfg, lp, h):
    """A differential-attention layer's ``(q (..., heads, hd), k, v (...,
    kv heads, hd))`` from its normalised input ``h`` (..., d), biased
    where the layer has biases; a cross-attention layer's k and v are
    None (it reads another layer's)."""
    hd = _head_dim(cfg)
    lead = h.shape[:-1]

    def proj(w, b):
        t = h @ lp[w]
        return (t + lp[b] if cfg.attn_bias else t).reshape(lead + (-1, hd))

    if "wk" not in lp:
        return proj("wq", "bq"), None, None
    return proj("wq", "bq"), proj("wk", "bk"), proj("wv", "bv")


def _diff_pairs(t):
    """K or V heads (..., kv heads, hd) as pairs (..., kv heads / 2, 2 hd):
    heads 2j and 2j + 1 side by side (a free reshape)."""
    return t.reshape(t.shape[:-2] + (t.shape[-2] // 2, 2 * t.shape[-1]))


def _diff_queries(q, pairs):
    """Query heads (..., heads, hd) as rows against ``pairs`` K/V pairs
    (..., pairs, 2 g, 2 hd), g differential heads a pair: row ``m g + e``
    of pair j is differential head ``i = j g + e``'s query of map ``m``,
    ``[q_2i | 0]`` (map 1 reads K head 2j) or ``[0 | q_2i+1]`` (map 2
    reads K head 2j + 1). Against a pair's 2 hd wide key it scores its
    own map alone, and its output is that map over the pair's whole
    value: ONE grouped attention over the pairs gives both maps of every
    head from one read of each key and value."""
    lead, (nh, hd) = q.shape[:-2], q.shape[-2:]
    g = nh // 2 // pairs
    qr = q.reshape(lead + (pairs, g, 2, hd))
    zero = jnp.zeros_like(qr[..., 0, :])
    return jnp.stack([jnp.concatenate([qr[..., 0, :], zero], -1),
                      jnp.concatenate([zero, qr[..., 1, :]], -1)],
                     axis=-3).reshape(lead + (pairs, 2 * g, 2 * hd))


def _diff_lambda(lp, li):
    """``(lambda, lambda_init)`` of layer ``li`` (its depth): ``exp(l_q1 .
    l_k1) - exp(l_q2 . l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6
    exp(-0.3 li)``, float32."""
    f32 = jnp.float32
    init = 0.8 - 0.6 * math.exp(-0.3 * li)
    dot = lambda a, b: jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))
    return (jnp.exp(dot("lambda_q1", "lambda_k1"))
            - jnp.exp(dot("lambda_q2", "lambda_k2")) + init), init


def _diff_out(cfg, lp, li, o):
    """A differential-attention layer's output from the two maps' reads
    ``o`` (..., pairs, 2 g, 2 hd) (:func:`_diff_queries`' rows): a head's
    ``A1 v - lambda A2 v``, an RMSNorm over its 2 hd with a gain, times
    ``1 - lambda_init``, the heads side by side through the biased output
    map."""
    f32 = jnp.float32
    lead, (pairs, g2, w) = o.shape[:-3], o.shape[-3:]
    o = o.reshape(lead + (pairs, 2, g2 // 2, w)).astype(f32)
    lam, init = _diff_lambda(lp, li)
    d = o[..., 0, :, :] - lam * o[..., 1, :, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + cfg.norm_eps)
    d = d * lp["subln_g"].astype(f32) * (1.0 - init)
    out = d.reshape(lead + (-1,)).astype(lp["wo"].dtype) @ lp["wo"]
    return out + lp["bo"] if cfg.attn_bias else out


def _diff_attend_seq(cfg, q, k, v, window=None, lengths=None):
    """Differential attention over whole sequences in ``jax.numpy``: q
    (b, s_q, heads, hd) at the LAST s_q positions of k, v (b, s, kv
    heads, hd); causal, within ``window`` where given, keys past a row's
    ``lengths`` masked. Returns the maps' reads (b, s_q, pairs, 2 g, 2
    hd) for :func:`_diff_out`."""
    pairs = k.shape[-2] // 2
    qr = _diff_queries(q, pairs)
    kp, vp = _diff_pairs(k), _diff_pairs(v)
    s_q, s = q.shape[1], k.shape[1]
    sc = jnp.einsum("bqpgd,bkpd->bpgqk", qr, kp) / np.sqrt(_head_dim(cfg))
    qpos = jnp.arange(s - s_q, s)[:, None]
    kpos = jnp.arange(s)[None, :]
    seen = kpos <= qpos
    if window is not None:
        seen = jnp.logical_and(seen, qpos - kpos < window)
    seen = seen[None, None, None]
    if lengths is not None:
        seen = jnp.logical_and(
            seen, (kpos < lengths[:, None])[:, None, None, None])
    sc = jnp.where(seen, sc, -1e30)
    return jnp.einsum("bpgqk,bkpd->bqpgd", jax.nn.softmax(sc, -1), vp)


def _diff_attend_prompt(cfg, q, k, v, window):
    """The prefill's differential self-attention: (b, s, pairs, 2 g, 2
    hd) as :func:`_diff_attend_seq`, through the flash prefill kernel on
    the TPU (``ops/pallas/diff_attention.diff_flash_prefill``)."""
    if not on_tpu(q):
        return _diff_attend_seq(cfg, q, k, v, window)
    from ..ops.pallas.diff_attention import diff_flash_prefill
    pairs = k.shape[-2] // 2
    qr = _diff_queries(q, pairs)
    b, s = q.shape[:2]
    o = diff_flash_prefill(qr.reshape(b, s, -1, qr.shape[-1]),
                           _diff_pairs(k), _diff_pairs(v),
                           1.0 / np.sqrt(_head_dim(cfg)), window)
    return o.reshape(qr.shape)


def _diff_attend_token(cfg, part, li, q, pos_b, window):
    """One token a row of differential attention over layer ``li`` of a
    paged cache of K/V pairs: q (b, heads, hd) -> (b, pairs, 2 g, 2 hd)
    float32 (``ops/pallas/diff_attention.diff_paged_decode``: each live
    page read once for both maps; the two maps' reads come out float32,
    since their difference cancels most of them)."""
    from ..ops.pallas.diff_attention import diff_paged_decode
    pairs = _kv_heads(cfg) // 2
    return diff_paged_decode(
        _diff_queries(q.astype(jnp.float32), pairs), part.k_pages,
        part.v_pages,
        part.block_tables, pos_b + 1, part.page_size,
        1.0 / np.sqrt(_head_dim(cfg)), window=window, layer=li)


def transformer_forward_single(params, tokens, cfg: TransformerConfig,
                               with_stats=False):
    """Single-device reference forward (used by tests to validate the
    sharded step; also the flagship single-chip inference path).
    ``with_stats`` also returns :func:`_stats`' dict."""
    _validate_config(cfg)
    x = params["embed"][tokens]
    if cfg.pos_type == "learned":
        x = x + params["pos"][: tokens.shape[1]]
    hd = _head_dim(cfg)
    groups = cfg.n_heads // _kv_heads(cfg)
    per_layer = []
    memory = shared = None      # what the cross-decoder reads
    for li_flat, layers, at, lp in _iter_layers(params, cfg):
        h = _norm(cfg, lp, "ln1", x)
        b, s, d = h.shape
        x_in = x
        kind, rotary, window = _layer_rule(cfg, li_flat)
        if _is_mla(cfg):
            x = x + _mla_attend_prompt(
                cfg, lp, *_mla_compress(cfg, lp, h, jnp.arange(s)[None, :]))
        elif kind == "linear":
            x = x + _gdn_prompt(cfg, lp, h)[0]
        elif kind == "mamba":
            out, memory, _state, _tail = _mamba_prompt(cfg, lp, h)
            x = x + out
        elif kind == "gmu":
            x = x + _gmu(lp, h, memory)
        elif cfg.diff_attn:
            q, k, v = _diff_qkv(cfg, lp, h)
            if k is None:
                k, v = shared
            shared = k, v
            x = x + _diff_out(cfg, lp, li_flat,
                              _diff_attend_seq(cfg, q, k, v, window))
        else:
            q, k, v, gate = _attn_qkv(cfg, lp, h, jnp.arange(s)[None, :],
                                      rotary)
            k, v = _expand_kv(k, groups, 2), _expand_kv(v, groups, 2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            sc = jnp.where(_causal_mask(s, window), sc, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + _attn_out(lp, o.reshape(b, s, cfg.n_heads * hd), gate)
        f, st_l = _ffn(cfg, lp, _norm(cfg, lp, "ln2", x), x_in, layers, at)
        per_layer.append(st_l)
        x = x + f
    logits = _logits(cfg, params, x)
    return (logits, _stats(cfg, per_layer)) if with_stats else logits


# ---------------------------------------------------------------------------
# KV-cache autoregressive decode (TPU-first addition: the reference's
# inference story is feedforward/RNN serving; a transformer framework
# needs an O(1)-per-token decode path. Static shapes throughout, in one
# of two layouts behind a shared attention path:
#
# * DENSE — dict of (layers, b, kv_heads, max_len, hd) arrays, one
#   contiguous strip per sequence (training-time eval, tests, the
#   single-prompt generate loop);
# * PAGED — :class:`PagedKVCache`: a shared pool of fixed-size pages
#   (layers, num_pages, page_size, kv_heads, hd) plus per-row block
#   tables, so a serving engine can grow/retire sequences at page
#   granularity while every decode step keeps ONE compiled shape
#   (serve/decode.py; allocation lives in serve/kv_pages.py).
#
# Both layouts share `_cache_attend` (mask + GQA softmax math), so the
# paged serving path is numerically the dense path — the acceptance
# tests assert bitwise equality.
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: TransformerConfig, batch, max_len=None):
    """Zeroed K/V cache: dict of (layers, b, KV heads, max_len, hd) —
    GQA stores only the shared heads, an n_heads/n_kv_heads memory
    saving at long context."""
    max_len = max_len or cfg.max_len
    if _is_mla(cfg):
        raise ValueError("a latent-attention model (kv_lora_rank > 0) has "
                         "no dense K/V strip: it decodes over the paged "
                         "latent cache (init_kv_pages)")
    if _has_state(cfg):
        raise ValueError("a model with recurrent (linear-attention or "
                         "Mamba) layers has no dense K/V strip: it decodes "
                         "over pages and state rows (init_kv_pages, "
                         "LinearStateCache)")
    hd = _head_dim(cfg)
    # layer stacking mirrors the params layout (pp, lps, ...)
    n_l = cfg.n_layers
    shape = (n_l, batch, _kv_heads(cfg), max_len, hd)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


class PagedKVCache(object):
    """Paged KV-cache view: pooled pages + per-row block tables.

    ``k_pages``/``v_pages``: (layers, num_pages, page_size, kv_heads,
    hd) — the HBM pool, preallocated once and shared by every live
    sequence; a differential-attention model's are (layers, num_pages,
    page_size * kv pairs, 2 hd), a page's (token, pair) rows flat, so
    that a page is whole memory tiles (:func:`init_kv_pages`).
    ``block_tables``: (b, pages_per_seq) int32 — position ``p`` of row
    ``r`` lives at page ``block_tables[r, p // page_size]`` offset ``p %
    page_size``. A registered pytree (page_size is static
    aux data), so it traces straight through jit with the pool arrays
    donated.

    On the TPU the pool stays whole from the program's arguments to its
    results: the paged kernels take it with the layer's index and read
    and write that layer's pages in place. A ``k_pages[layer]`` in front
    of a Mosaic call is never free — XLA has no view of an array for a
    custom call's operand, so the slice is a copy of one layer of the
    pool per call, and ``.at[layer].set`` of a kernel's result another
    (the whole pool once a token step: 63 % of a decode step's device
    time before the kernels took the index). Only the CPU twins index
    the pool by layer, inside fusions XLA owns.
    """

    __slots__ = ("k_pages", "v_pages", "block_tables", "page_size")

    def __init__(self, k_pages, v_pages, block_tables, page_size):
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.block_tables = block_tables
        self.page_size = int(page_size)

    @property
    def max_context(self):
        """Positions addressable per row via the block table."""
        return self.block_tables.shape[1] * self.page_size


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((c.k_pages, c.v_pages, c.block_tables), c.page_size),
    lambda ps, ch: PagedKVCache(ch[0], ch[1], ch[2], ps))


class HybridKVCache(object):
    """Two kinds of layer in one cache: ``full`` is a
    :class:`PagedKVCache` over the model's global layers (every position
    kept, as ever), ``window`` one over its sliding-window layers, whose
    block tables are RINGS of ``window / page_size + 1`` entries —
    position ``p`` lives at entry ``(p // page_size) % entries``, so a
    sequence holds at most a window's worth of pages there however long
    it grows. Each kind has a pool of its own, stacked over that kind's
    layers in layer order (:func:`kv_layer_kinds`). A prefill into a
    hybrid cache writes only the pages that hold a real position (by
    ``lengths``), so a row needs pages for its tokens, not its bucket."""

    __slots__ = ("full", "window")

    def __init__(self, full, window):
        self.full = full
        self.window = window


jax.tree_util.register_pytree_node(
    HybridKVCache,
    lambda c: ((c.full, c.window), None),
    lambda _aux, ch: HybridKVCache(ch[0], ch[1]))


class LatentKVCache(object):
    """The paged cache of a latent-attention (MLA) model: ONE pool and
    no V pool. A token's vector in a layer is its normalised compressed
    KV followed by the rotated key all heads share, kv_lora_rank +
    qk_rope_head_dim values; ``pages`` holds them transposed, in lane
    tiles: (layers, num_pages, page_size / lt, width, lt)
    (``ops/pallas/mla_attention.py`` says why);
    ``block_tables`` as in :class:`PagedKVCache`. The prefill writes
    only the pages that hold a real position (by ``lengths``), and
    attends the prompt decompressed per head; the decode step attends the
    pool absorbed, in place by layer index
    (``ops/pallas/mla_attention.py``). In a program's arguments the
    pool rides where ``k_pages`` does, with ``v_pages`` None."""

    __slots__ = ("pages", "block_tables", "page_size")

    def __init__(self, pages, block_tables, page_size):
        self.pages = pages
        self.block_tables = block_tables
        self.page_size = int(page_size)

    @property
    def max_context(self):
        return self.block_tables.shape[1] * self.page_size


jax.tree_util.register_pytree_node(
    LatentKVCache,
    lambda c: ((c.pages, c.block_tables), c.page_size),
    lambda ps, ch: LatentKVCache(ch[0], ch[1], ps))


class LinearStateCache(object):
    """The cache of a model with recurrent layers (Gated DeltaNet or
    Mamba) among attention ones: ``full`` is the cache of the attention
    layers alone — a :class:`PagedKVCache` over the full layers (in layer
    order, :func:`kv_layer_kinds`), or a :class:`HybridKVCache` where
    there are window layers too — and a recurrent layer keeps, for every
    sequence, a fixed-size STATE in a row of two pools: ``state`` (layers
    of the kind, rows, ...) float32, the recurrence's matrices — (value
    heads, dk, dv) a Gated DeltaNet layer's, (d_state, channels) a
    Mamba layer's — and ``conv`` (layers, rows, (width - 1) * channels),
    the last inputs of the layer's causal convolution, flat so that the
    minor dim fills whole lane tiles.
    ``rows`` (b,) int32 names each batch row's state row; row 0 is the
    NULL ROW, what a step bucket's dummy slots read and write, as dummy
    slots write the null page. A state row does not grow with the
    sequence, and is the sequence's for its life. In a program's
    arguments the pools ride as pairs: ``k_pages = (full K, state)``,
    ``v_pages = (full V, conv)``, ``block_tables = (full table, rows (b,
    1))``, where "full" is itself a (full, window) pair for a model with
    window layers (:func:`paged_cache`, :func:`init_kv_pages`)."""

    __slots__ = ("full", "state", "conv", "rows")

    def __init__(self, full, state, conv, rows):
        self.full = full
        self.state = state
        self.conv = conv
        self.rows = rows


jax.tree_util.register_pytree_node(
    LinearStateCache,
    lambda c: ((c.full, c.state, c.conv, c.rows), None),
    lambda _aux, ch: LinearStateCache(*ch))


def _check_latent(cfg, cache):
    if _is_mla(cfg) != isinstance(cache, LatentKVCache):
        raise ValueError(
            "a latent-attention model (kv_lora_rank > 0) runs over a "
            "LatentKVCache and no other model does (init_kv_pages + "
            "paged_cache build the right one); got %s"
            % type(cache).__name__)
    if _has_state(cfg) != isinstance(cache, LinearStateCache):
        raise ValueError(
            "a model with recurrent (linear-attention or Mamba) layers "
            "runs over a "
            "LinearStateCache and no other model does (init_kv_pages + "
            "paged_cache(cfg=) build the right one); got %s"
            % type(cache).__name__)


def paged_cache(k_pages, v_pages, block_tables, page_size, cfg=None):
    """The cache view a program builds from its arguments: one pool and
    one table, for a model with window layers a ``(full, window)`` pair
    of each, for a latent-attention model its one latent pool and None,
    for a model with recurrent layers (told by ``cfg``) the attention
    layers' pools (one, or a pair) paired with the state pools and their
    table(s) with the state rows (what :func:`init_kv_pages` returns in
    each case)."""
    if v_pages is None:
        return LatentKVCache(k_pages, block_tables, page_size)
    if cfg is not None and _has_state(cfg):
        return LinearStateCache(
            paged_cache(k_pages[0], v_pages[0], block_tables[0],
                        page_size),
            k_pages[1], v_pages[1], block_tables[1].reshape(-1))
    if isinstance(k_pages, (tuple, list)):
        return HybridKVCache(*(PagedKVCache(k, v, bt, page_size)
                               for k, v, bt in zip(k_pages, v_pages,
                                                   block_tables)))
    return PagedKVCache(k_pages, v_pages, block_tables, page_size)


def cache_pools(cache):
    """``(k_pages, v_pages)`` back out of :func:`paged_cache`'s view."""
    if isinstance(cache, LatentKVCache):
        return cache.pages, None
    if isinstance(cache, LinearStateCache):
        k, v = cache_pools(cache.full)
        return (k, cache.state), (v, cache.conv)
    if isinstance(cache, HybridKVCache):
        return ((cache.full.k_pages, cache.window.k_pages),
                (cache.full.v_pages, cache.window.v_pages))
    return cache.k_pages, cache.v_pages


def _kind_index(cfg, li):
    """Layer ``li``'s index among the layers of its own kind (the layer
    axis of that kind's pools)."""
    kinds = kv_layer_kinds(cfg)
    return kinds[:li].count(kinds[li])


def _layer_cache(cache, cfg, li):
    """``(cache that holds layer li, the layer's index in it, put)``;
    ``put(c)`` is the whole cache with that part replaced. A cross-
    attention layer holds nothing: it is given the pages of the layer
    whose K/V it reads (:func:`_yoco_sources`), and its ``put`` keeps
    the cache as it was."""
    kinds = kv_layer_kinds(cfg)
    if kinds[li] == "cross":
        part, idx, _put = _layer_cache(cache, cfg, _yoco_sources(cfg)[0])
        return part, idx, lambda c: cache
    if isinstance(cache, LinearStateCache):
        part, idx, put = _layer_cache(cache.full, cfg, li)
        if not isinstance(cache.full, HybridKVCache):
            idx = _kind_index(cfg, li)
        return part, idx, lambda c: LinearStateCache(
            put(c), cache.state, cache.conv, cache.rows)
    if not isinstance(cache, HybridKVCache):
        return cache, li, lambda c: c
    idx = _kind_index(cfg, li)
    if kinds[li] == "window":
        return cache.window, idx, lambda c: HybridKVCache(cache.full, c)
    return cache.full, idx, lambda c: HybridKVCache(c, cache.window)


def init_kv_pages(cfg: TransformerConfig, num_pages, page_size):
    """Zeroed page pool ``(k_pages, v_pages)``, each (layers,
    num_pages, page_size, kv_heads, hd). Sized once at engine start:
    HBM cost is 2 * layers * num_pages * page_size * kv_heads * hd *
    itemsize, independent of live traffic. ``num_pages`` as a ``(full,
    window)`` pair gives a pair of each, one pool a kind of layer
    (:class:`HybridKVCache`). A latent-attention model has ONE pool of
    kv_lora_rank + qk_rope_head_dim values a token a layer
    (``mla_attention.latent_pool_shape``) and no V pool: ``(pages,
    None)`` (:class:`LatentKVCache`). A model with linear layers takes
    ``num_pages`` as ``(pages, state rows)`` and gives ``((K pool of the
    full layers, state pool), (V pool, convolution tails))``
    (:class:`LinearStateCache`; row 0 is the null row); with window layers
    too its ``num_pages`` is ``((full pages, window pages), state rows)``
    and the two attention pools are pairs. A differential-attention
    model's pages are flat: (layers, num_pages, page_size * kv pairs, 2
    hd) — a pair of 64-wide heads fills a 128-lane tile, and a page of
    whole tiles can be copied by the decode kernel (``ops/pallas/
    diff_attention.py``)."""
    if _is_mla(cfg):
        from ..ops.pallas.mla_attention import latent_pool_shape
        return jnp.zeros(latent_pool_shape(
            cfg.n_layers, num_pages, page_size,
            cfg.kv_lora_rank + cfg.qk_rope_head_dim), cfg.dtype), None
    hd = _head_dim(cfg)

    def pool(n_layers, n_pages):
        shape = (n_layers, int(n_pages), int(page_size), _kv_heads(cfg),
                 hd)
        if cfg.diff_attn:
            shape = (n_layers, int(n_pages),
                     int(page_size) * _kv_heads(cfg) // 2, 2 * hd)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)

    kinds = kv_layer_kinds(cfg)

    def full_and_window(spec):
        (kf, vf), (kw, vw) = (pool(kinds.count(kind), n) for kind, n in
                              zip(("full", "window"), spec))
        return (kf, kw), (vf, vw)

    if _has_state(cfg):
        if not isinstance(num_pages, (tuple, list)):
            raise ValueError("a model with recurrent layers needs "
                             "num_pages as (pages of the attention "
                             "layers, state rows)")
        rows = int(num_pages[1])
        if "window" in kinds:
            if not isinstance(num_pages[0], (tuple, list)):
                raise ValueError("a model with window and recurrent layers "
                                 "needs num_pages as ((full pages, window "
                                 "pages), state rows)")
            kf, vf = full_and_window(num_pages[0])
        else:
            kf, vf = pool(kinds.count("full"), num_pages[0])
        if "mamba" in kinds:
            n_st, channels = kinds.count("mamba"), \
                cfg.mamba_expand * cfg.d_model
            state = (cfg.mamba_d_state, channels)
            taps = cfg.mamba_d_conv
        else:
            n_st = kinds.count("linear")
            channels = (2 * cfg.linear_key_heads * cfg.linear_key_dim
                        + cfg.linear_value_heads * cfg.linear_value_dim)
            state = (cfg.linear_value_heads, cfg.linear_key_dim,
                     cfg.linear_value_dim)
            taps = cfg.linear_conv_width
        return ((kf, jnp.zeros((n_st, rows) + state, jnp.float32)),
                (vf, jnp.zeros((n_st, rows, (taps - 1) * channels),
                               cfg.dtype)))
    if isinstance(num_pages, (tuple, list)):
        return full_and_window(num_pages)
    return pool(cfg.n_layers, num_pages)


def _positions_vec(pos, b):
    """Per-row positions (b,) from a scalar (legacy: whole batch at one
    position) or per-row vector (ragged continuous-batching decode)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    return pos


def _cache_write_token(cache, li, k_t, v_t, pos_b, window=None):
    """Write one token's K/V (b, kv_heads, hd) at per-row positions —
    the single place the two cache layouts diverge on the write path.
    A window layer's block table is a ring (the dense strip keeps
    everything and masks)."""
    if isinstance(cache, PagedKVCache):
        entry = pos_b // cache.page_size
        if window is not None:
            entry = entry % cache.block_tables.shape[1]
        page = jnp.take_along_axis(
            cache.block_tables, entry[:, None], axis=1)[:, 0]
        off = pos_b % cache.page_size
        if cache.k_pages.ndim == 4:     # a page's (token, head) rows flat
            kvh = k_t.shape[1]
            page, off = page[:, None], off[:, None] * kvh + jnp.arange(kvh)
        return PagedKVCache(
            cache.k_pages.at[li, page, off].set(
                k_t.astype(cache.k_pages.dtype)),
            cache.v_pages.at[li, page, off].set(
                v_t.astype(cache.v_pages.dtype)),
            cache.block_tables, cache.page_size)
    rows = jnp.arange(k_t.shape[0])
    return {"k": cache["k"].at[li, rows, :, pos_b].set(
                k_t.astype(cache["k"].dtype)),
            "v": cache["v"].at[li, rows, :, pos_b].set(
                v_t.astype(cache["v"].dtype))}


def _cache_attend(cache, li, q, pos_b, cfg, window=None):
    """One-token GQA attention against layer ``li`` of either cache
    layout: q (b, n_heads, hd) -> context (b, d_model). Grouped heads
    attend the compact cache directly (expanding it per step would
    materialize the very tensor GQA exists to avoid); rows see
    positions <= their own pos, so ragged batches never read a
    neighbour's (or their own stale) tail."""
    b, nh, hd = q.shape
    kvh = _kv_heads(cfg)
    if isinstance(cache, PagedKVCache):
        if on_tpu(q):
            from ..ops.pallas.flash_attention import paged_decode_attention
            o = paged_decode_attention(
                q.reshape(b, kvh, nh // kvh, hd),
                cache.k_pages, cache.v_pages,
                cache.block_tables, pos_b + 1,
                sm_scale=1.0 / np.sqrt(hd), window=window, layer=li)
            return o.reshape(b, nh * hd)
        # pure-lax gather fallback (CPU tier-1): block-table gather
        # materializes the same (b, kvh, L, hd) view the dense layout
        # slices, then the shared math below runs unchanged
        kc = cache.k_pages[li][cache.block_tables]
        vc = cache.v_pages[li][cache.block_tables]
        L = kc.shape[1] * kc.shape[2]
        kc = kc.reshape(b, L, kvh, hd).transpose(0, 2, 1, 3)
        vc = vc.reshape(b, L, kvh, hd).transpose(0, 2, 1, 3)
    else:
        kc = cache["k"][li]                   # (b, kvh, max_len, hd)
        vc = cache["v"][li]
        L = kc.shape[2]
    if window is None:
        visible = jnp.arange(L)[None, :] <= pos_b[:, None]  # (b, L)
    else:
        if isinstance(cache, PagedKVCache):
            from ..ops.pallas.flash_attention import ring_positions
            kpos = ring_positions(cache.block_tables.shape[1],
                                  cache.page_size, pos_b + 1)
        else:
            kpos = jnp.arange(L)[None, :]
        visible = jnp.logical_and(
            jnp.logical_and(kpos >= 0, kpos <= pos_b[:, None]),
            kpos > pos_b[:, None] - window)
    qg = q.reshape(b, kvh, nh // kvh, hd)
    sc = jnp.einsum("bkgd,bkld->bkgl", qg, kc) / np.sqrt(hd)
    sc = jnp.where(visible[:, None, None, :], sc, -1e30)
    o = jnp.einsum("bkgl,bkld->bkgd", jax.nn.softmax(sc, -1), vc)
    return o.reshape(b, nh * hd)


def transformer_decode_step(params, cache, tokens_t, pos,
                            cfg: TransformerConfig, with_stats=False):
    """One decode step: tokens_t (b,) int32 at position(s) ``pos`` ->
    (logits (b, V), updated cache).

    ``pos`` is a traced scalar (whole batch at one position — the
    single-prompt generate loop) or a traced (b,) vector of per-row
    positions (continuous batching: every slot at its own depth).
    ``cache`` is the dense dict from :func:`init_kv_cache` or a
    :class:`PagedKVCache`; either way attention reads a fixed-shape
    view under a <= pos mask, so the step compiles once per (batch,
    layout) and never again. A model with window layers takes a
    :class:`HybridKVCache` (or the dense dict, which keeps everything
    and masks). ``with_stats`` also returns :func:`_stats`' dict."""
    b = tokens_t.shape[0]
    pos_b = _positions_vec(pos, b)
    _check_latent(cfg, cache)

    x = params["embed"][tokens_t]                     # (b, d)
    if cfg.pos_type == "learned":
        x = x + params["pos"][pos_b]                  # (b, d) gather
    per_layer = []
    memory = None               # the last Mamba layer's, for the GMUs
    for li_flat, layers, at, lp in _iter_layers(params, cfg):
        h = _norm(cfg, lp, "ln1", x)
        x_in = x
        kind, rotary, window = _layer_rule(cfg, li_flat)
        if _is_mla(cfg):
            c_q, latent_t = _mla_compress(cfg, lp, h, pos_b)
            cache = _latent_write_token(cache, li_flat, latent_t, pos_b)
            x = x + _mla_attend_latent(cfg, lp, c_q, cache, li_flat, pos_b)
        elif kind == "linear":
            out, cache = _gdn_token(cfg, lp, h, cache,
                                    _kind_index(cfg, li_flat))
            x = x + out
        elif kind == "mamba":
            out, memory, cache = _mamba_token(cfg, lp, h, cache,
                                              _kind_index(cfg, li_flat))
            x = x + out
        elif kind == "gmu":
            x = x + _gmu(lp, h, memory)
        elif cfg.diff_attn:
            q, k_t, v_t = _diff_qkv(cfg, lp, h)
            part, part_li, put = _layer_cache(cache, cfg, li_flat)
            if k_t is not None:         # a cross layer writes nothing
                part = _cache_write_token(part, part_li, _diff_pairs(k_t),
                                          _diff_pairs(v_t), pos_b, window)
                cache = put(part)
            x = x + _diff_out(cfg, lp, li_flat, _diff_attend_token(
                cfg, part, part_li, q, pos_b, window))
        else:
            q, k_t, v_t, gate = _attn_qkv(cfg, lp, h, pos_b, rotary)
            part, part_li, put = _layer_cache(cache, cfg, li_flat)
            part = _cache_write_token(part, part_li, k_t, v_t, pos_b,
                                      window)
            o = _cache_attend(part, part_li, q, pos_b, cfg, window)
            cache = put(part)
            x = x + _attn_out(lp, o, gate)
        f, st_l = _ffn(cfg, lp, _norm(cfg, lp, "ln2", x), x_in, layers, at)
        per_layer.append(st_l)
        x = x + f
    logits = _logits(cfg, params, x)
    if with_stats:
        return logits, cache, _stats(cfg, per_layer)
    return logits, cache


def _cache_write_prompt(cache, li, kg, vg, lengths=None, window=None):
    """Write a prompt's K/V (b, s, kv_heads, hd) for layer ``li`` into
    either cache layout — the prefill counterpart of
    :func:`_cache_write_token`. ``lengths`` / ``window`` choose the
    pages as ``flash_attention.prefill_page_dest`` says."""
    b, s, hk, hd = kg.shape
    if isinstance(cache, PagedKVCache):
        ps = cache.page_size
        if s % ps:
            raise ValueError("prefill bucket %d is not a multiple of "
                             "page_size %d" % (s, ps))
        n_pb = s // ps
        if window is None and n_pb > cache.block_tables.shape[1]:
            raise ValueError("prefill bucket %d needs %d pages/row; "
                             "block table holds %d"
                             % (s, n_pb, cache.block_tables.shape[1]))
        # (b, s, hk, hd) -> (b, pages, page_size, hk, hd): position j
        # of row r scatters to page block_tables[r, j // ps] offset
        # j % ps — one reshape, one scatter per layer
        from ..ops.pallas.flash_attention import prefill_page_dest
        bt = prefill_page_dest(cache.block_tables, n_pb, ps, lengths,
                               window)
        page = ((b, n_pb, ps * hk, hd) if cache.k_pages.ndim == 4
                else (b, n_pb, ps, hk, hd))
        return PagedKVCache(
            cache.k_pages.at[li, bt].set(
                kg.reshape(page).astype(cache.k_pages.dtype)),
            cache.v_pages.at[li, bt].set(
                vg.reshape(page).astype(cache.v_pages.dtype)),
            cache.block_tables, cache.page_size)
    # (b, s, hk, d) -> dense layout (b, hk, s, d), written [:s]
    return {"k": cache["k"].at[li, :, :, :s].set(
                kg.transpose(0, 2, 1, 3).astype(cache["k"].dtype)),
            "v": cache["v"].at[li, :, :, :s].set(
                vg.transpose(0, 2, 1, 3).astype(cache["v"].dtype))}


def _prefill_impl(params, tokens, cache, cfg, lengths, with_stats=False):
    """Shared prefill body for both cache layouts: one batched causal
    forward computes and caches every prompt position's K/V. With
    ``lengths`` (b,) the returned logits are each row's last REAL
    position (right-padded ragged prompts); without, position -1."""
    b, s = tokens.shape
    hd = _head_dim(cfg)
    _check_latent(cfg, cache)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    # a hybrid cache's rows (and a latent one's) hold pages for their
    # tokens, not for their bucket: its page writes go by the real lengths
    write_lengths = lengths if isinstance(
        cache, (HybridKVCache, LatentKVCache, LinearStateCache)) else None

    x = params["embed"][tokens]
    if cfg.pos_type == "learned":
        x = x + params["pos"][:s]
    mask = jnp.tril(jnp.ones((s, s), bool))
    per_layer = []
    memory = shared = None      # what the cross-decoder reads
    last = (jnp.full((b,), s, jnp.int32) if lengths is None
            else lengths) - 1
    for li_flat, layers, at, lp in _iter_layers(params, cfg):
        if li_flat == cfg.cross_from:
            # the cross-decoder caches nothing and a position sees only
            # itself and the K/V layer's keys: the prompt's last REAL
            # position alone runs it (YOCO's prefill)
            x = jnp.take_along_axis(x, last[:, None, None], 1)
            if memory is not None:
                memory = jnp.take_along_axis(memory, last[:, None, None], 1)
        h = _norm(cfg, lp, "ln1", x)
        x_in = x
        kind, rotary, window = _layer_rule(cfg, li_flat)
        if _is_mla(cfg):
            # the cache takes the latents; the attend decompresses the
            # very same ones per head (the decode step attends them
            # absorbed: two paths over one cache)
            c_q, latent = _mla_compress(cfg, lp, h, jnp.arange(s)[None, :])
            cache = _latent_write_prompt(cache, li_flat, latent,
                                         write_lengths)
            x = x + _mla_attend_prompt(cfg, lp, c_q, latent)
        elif kind == "linear":
            # the rows' state and convolution tail as they stand after
            # each prompt's REAL length go into the rows' state rows
            out, state, tail = _gdn_prompt(cfg, lp, h, lengths)
            lin = _kind_index(cfg, li_flat)
            cache = LinearStateCache(
                cache.full, cache.state.at[lin, cache.rows].set(state),
                cache.conv.at[lin, cache.rows].set(
                    tail.reshape(b, -1).astype(cache.conv.dtype)),
                cache.rows)
            x = x + out
        elif kind == "mamba":
            # as a linear layer: the state and tail after each REAL length
            out, memory, state, tail = _mamba_prompt(cfg, lp, h, lengths)
            lin = _kind_index(cfg, li_flat)
            cache = LinearStateCache(
                cache.full, cache.state.at[lin, cache.rows].set(state),
                cache.conv.at[lin, cache.rows].set(
                    tail.reshape(b, -1).astype(cache.conv.dtype)),
                cache.rows)
            x = x + out
        elif kind == "gmu":
            x = x + _gmu(lp, h, memory)
        elif kind == "cross":
            q, _k, _v = _diff_qkv(cfg, lp, h)
            x = x + _diff_out(cfg, lp, li_flat, _diff_attend_seq(
                cfg, q, *shared, lengths=last + 1))
        elif cfg.diff_attn:
            q, kg, vg = _diff_qkv(cfg, lp, h)
            shared = kg, vg
            part, part_li, put = _layer_cache(cache, cfg, li_flat)
            cache = put(_cache_write_prompt(
                part, part_li, _diff_pairs(kg), _diff_pairs(vg),
                write_lengths, window))
            x = x + _diff_out(cfg, lp, li_flat, _diff_attend_prompt(
                cfg, q, kg, vg, window))
        else:
            # rotate BEFORE caching: decode stores rotated keys, so
            # prefill must too (q rotates here as well)
            q, kg, vg, gate = _attn_qkv(cfg, lp, h, jnp.arange(s)[None, :],
                                        rotary)
            part, part_li, put = _layer_cache(cache, cfg, li_flat)
            if isinstance(part, PagedKVCache) and on_tpu(q):
                # fused Pallas prefill: one program computes the causal
                # attention AND writes this layer's pages of the whole
                # pool in its DMA epilogue (what it returns IS the new
                # pool) — the kernel's lax twin is op-for-op the
                # _cache_write_prompt + expand/einsum branch below, so
                # CPU tier-1 (and dense==paged) semantics are that path
                from ..ops.pallas.flash_attention import (
                    flash_prefill_paged)
                o, kp, vp = flash_prefill_paged(
                    q, kg, vg, part.k_pages, part.v_pages,
                    part.block_tables, lengths=write_lengths,
                    window=window, layer=part_li)
                part = PagedKVCache(kp, vp, part.block_tables,
                                    part.page_size)
            else:
                part = _cache_write_prompt(part, part_li, kg, vg,
                                           write_lengths, window)
                groups = cfg.n_heads // _kv_heads(cfg)
                k = _expand_kv(kg, groups, 2)
                v = _expand_kv(vg, groups, 2)
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
                sc = jnp.where((mask if window is None
                                else _causal_mask(s, window))[None, None],
                               sc, -1e30)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(sc, -1), v)
            cache = put(part)
            x = x + _attn_out(lp, o.reshape(b, s, cfg.n_heads * hd), gate)
        f, st_l = _ffn(cfg, lp, _norm(cfg, lp, "ln2", x), x_in, layers, at)
        per_layer.append(st_l)
        x = x + f
    if cfg.cross_from is not None:
        xl = x[:, 0]
    elif lengths is None:
        xl = x[:, -1]
    else:
        # each row's last REAL position, not the padded tail
        xl = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                 axis=1)[:, 0]
    logits = _logits(cfg, params, xl)
    if with_stats:
        return logits, cache, _stats(cfg, per_layer)
    return logits, cache


def transformer_prefill(params, tokens, cache, cfg: TransformerConfig):
    """Fill the cache from a prompt with ONE batched causal forward —
    all prompt K/V per layer come from full-width matmuls (MXU-sized
    work), not s sequential decode steps. Returns (last_logits, cache).
    ``cache`` is the dense dict or a :class:`PagedKVCache` (the two
    layouts share this body; only the K/V write dispatches)."""
    return _prefill_impl(params, tokens, cache, cfg, lengths=None)


def transformer_prefill_paged(params, cache: PagedKVCache, tokens,
                              lengths, cfg: TransformerConfig,
                              with_stats=False):
    """Bucketed paged prefill: ONE batched causal forward fills each
    row's pages from its prompt and returns the logits each row needs
    to pick its first generated token.

    ``tokens``: (b, s) int32 prompts RIGHT-padded to the prefill
    bucket ``s`` (``s`` must be a multiple of ``cache.page_size``, so
    the page write is a pure reshape-scatter); ``lengths``: (b,) int32
    real prompt lengths. Returns (logits at each row's position
    ``lengths-1`` (b, V), updated cache). K/V of the padded tail land
    in the row's own reserved pages but are never visible — decode
    masks ``kpos <= pos`` — and causality keeps them out of every real
    position's forward, so the result is bitwise what an unpadded
    prefill computes. A :class:`HybridKVCache` does not even write the
    padded tail (it could wrap onto a window layer's ring), and its
    window layers keep the last ``ring`` pages of the real prompt.
    ``with_stats`` also returns :func:`_stats`' dict."""
    return _prefill_impl(params, tokens, cache, cfg, lengths=lengths,
                         with_stats=with_stats)


def transformer_decode_step_paged(params, k_pages, v_pages, block_tables,
                                  tokens_t, pos, cfg: TransformerConfig,
                                  page_size):
    """Page-table-consuming decode step (raw-array convenience over
    :func:`transformer_decode_step` + :class:`PagedKVCache`): returns
    (logits (b, V), k_pages, v_pages) so a serving engine can donate
    and rebind the pool arrays directly."""
    paged = PagedKVCache(k_pages, v_pages, block_tables, page_size)
    logits, paged = transformer_decode_step(params, paged, tokens_t,
                                            pos, cfg)
    return logits, paged.k_pages, paged.v_pages


# compiled generation programs, keyed on everything that shapes the
# trace — rebuilding the jitted closure per call would re-compile the
# whole prefill+decode program every time
_GENERATE_CACHE = {}


def _pick_token(logits, rng_t, temperature, top_k):
    """Next-token rule: greedy at temperature 0, else (top-k filtered)
    categorical sampling. Static branch — part of the compiled scan."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jnp.sort(scaled, axis=-1)[:, -int(top_k)][:, None]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    return jax.random.categorical(rng_t, scaled, axis=-1).astype(jnp.int32)


def _generate_program(cfg: TransformerConfig, b, s, steps, max_len,
                      temperature, top_k):
    key = (id(type(cfg)), repr(cfg), b, s, steps, max_len, temperature,
           top_k)
    fn = _GENERATE_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def run(params, prompt, rng):
        cache = init_kv_cache(cfg, b, max_len)
        logits, cache = transformer_prefill(params, prompt, cache, cfg)
        tok0 = _pick_token(logits, rng, temperature, top_k)

        def body(carry, t):
            cache, tok = carry
            logits, cache = transformer_decode_step(
                params, cache, tok, s + t, cfg)
            nxt = _pick_token(logits, jax.random.fold_in(rng, t),
                              temperature, top_k)
            return (cache, nxt), tok

        (_, _), toks = jax.lax.scan(
            body, (cache, tok0), jnp.arange(steps))
        return jnp.moveaxis(toks, 0, 1)               # (b, steps)

    _GENERATE_CACHE[key] = run
    return run


def transformer_generate(params, prompt, steps, cfg: TransformerConfig,
                         max_len=None, temperature=0.0, top_k=0, seed=0):
    """Generation: prompt (b, s) int32 -> (b, steps) int32. Greedy by
    default; ``temperature>0`` samples (optionally top-k filtered) from
    a fold_in-derived per-step PRNG stream. Prefill (one batched causal
    forward) + decode run as ONE jitted program, compiled once per
    (config, shape, decode rule) and cached; per-token decode cost is
    O(1) in generated length (KV cache, static shapes)."""
    b, s = prompt.shape
    max_len = max_len or cfg.max_len
    assert s + steps <= max_len, "prompt + steps exceeds max_len"
    fn = _generate_program(cfg, b, s, steps, max_len,
                           float(temperature), int(top_k))
    return fn(params, prompt, jax.random.PRNGKey(seed))
