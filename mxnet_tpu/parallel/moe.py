"""Mixture-of-Experts with expert parallelism over a mesh axis.

The reference has no MoE / expert parallelism (SURVEY.md §2.3 marks the
row absent); this is the TPU-first addition. Design follows the
GShard/Switch recipe adapted to XLA's strengths: routing is expressed
entirely as dense one-hot einsums (no gather/scatter, so dispatch and
combine both run on the MXU), experts are stacked on a leading axis
sharded over ``ep``, and the token→expert exchange is a psum over the
expert axis — XLA lowers the pattern to all-to-all/all-reduce on ICI.

Pieces:
* :func:`top_k_gating` — top-1/top-2 routing with per-expert capacity,
  position-in-expert via cumsum, and the GShard load-balancing aux loss;
* :func:`moe_apply` — dispatch → per-device expert FFN (vmapped over
  local experts) → combine, inside ``shard_map``.

The serving path of a model with many small experts has a second,
drop-free recipe on one device (:func:`moe_ffn_sorted`): top-k of E with
a softmax over the chosen, the assignments sorted by expert into padded
groups (:func:`sorted_dispatch`), one grouped product over the experts
(``ops/pallas/moe_ffn.py``) and a weighted gather back. It has no
capacity: every assignment is computed at any batch or prompt size.
:func:`group_limited_routing` is a second routing function for it
(sigmoid scores, a bias in the choice only, groups of experts), and a
device may hold only SOME of the experts (``first``): the router still
scores and chooses among all of them, the assignments to absent experts
take no row and add nothing, and the caller gets the held experts' part
of the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ["top_k_gating", "moe_apply", "stack_expert_params",
           "top_k_routing", "group_limited_routing", "sorted_dispatch",
           "moe_ffn_sorted"]


def stack_expert_params(params_list):
    """Stack per-expert pytrees on a leading ``num_experts`` axis
    (shard it P('ep'))."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def _one_hot(idx, n, dtype=jnp.float32):
    return (idx[..., None] == jnp.arange(n)).astype(dtype)


def top_k_gating(gate_logits, num_experts, capacity, k=2):
    """Compute dense dispatch/combine tensors for top-k routing.

    gate_logits : (tokens, num_experts).
    Returns (dispatch (n,E,C) in {0,1}, combine (n,E,C) float, aux_loss).
    """
    n = gate_logits.shape[0]
    gates = jax.nn.softmax(gate_logits, axis=-1)              # (n, E)

    idx1 = jnp.argmax(gates, axis=-1)                          # (n,)
    mask1 = _one_hot(idx1, num_experts)                        # (n, E)
    g1 = jnp.sum(gates * mask1, axis=-1)                       # (n,)

    # GShard load-balancing loss: E * sum_e mean(gates_e) * mean(tokens_e)
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux_loss = num_experts * jnp.sum(density * density_proxy)

    # position of each token within its expert-1 queue
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1           # (n, E)
    pos1_tok = jnp.sum(pos1, axis=-1)                          # (n,)
    kept1 = pos1_tok < capacity
    disp1 = (mask1 * kept1[:, None])[:, :, None] * \
        _one_hot(pos1_tok, capacity)[:, None, :]               # (n, E, C)

    if k >= 2:
        gates2 = gates * (1.0 - mask1)
        idx2 = jnp.argmax(gates2, axis=-1)
        mask2 = _one_hot(idx2, num_experts)
        g2 = jnp.sum(gates * mask2, axis=-1)
        # expert-2 queue continues after all expert-1 assignments
        pos2 = (jnp.cumsum(mask2, axis=0) - mask2
                + jnp.sum(mask1, axis=0, keepdims=True)) * mask2
        pos2_tok = jnp.sum(pos2, axis=-1)
        kept2 = pos2_tok < capacity
        disp2 = (mask2 * kept2[:, None])[:, :, None] * \
            _one_hot(pos2_tok, capacity)[:, None, :]
        denom = jnp.maximum(g1 + g2, 1e-9)
        w1, w2 = g1 / denom, g2 / denom
        dispatch = disp1 + disp2
        combine = w1[:, None, None] * disp1 + w2[:, None, None] * disp2
    else:
        dispatch = disp1
        combine = g1[:, None, None] * disp1
    return dispatch, combine, aux_loss


def _moe_local(expert_params, dispatch, combine, x, *, expert_fn, axis):
    """Per-device body: compute the local expert slice over ALL tokens.
    expert_params: (E_local, ...); dispatch/combine: (n, E_local, C);
    x: (n, d) replicated."""
    exp_in = jnp.einsum("nec,nd->ecd", dispatch, x)            # (El, C, d)
    exp_out = jax.vmap(expert_fn)(expert_params, exp_in)       # (El, C, d')
    partial = jnp.einsum("nec,ecd->nd", combine, exp_out)      # (n, d')
    return jax.lax.psum(partial, axis)


def moe_apply(x, gate_w, expert_params, expert_fn, mesh=None, axis="ep",
              k=2, capacity_factor=2.0):
    """Apply a sharded MoE layer to tokens ``x`` (tokens, d_model).

    gate_w : (d_model, num_experts) router weights.
    expert_params : pytree stacked on a leading num_experts axis
        (see :func:`stack_expert_params`); sharded P(axis).
    expert_fn : ``expert_fn(one_expert_params, (C, d)) -> (C, d_out)``.

    Returns (out (tokens, d_out), aux_loss).
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("moe_apply needs a Mesh (parallel.make_mesh)")
    n, _ = x.shape
    num_experts = gate_w.shape[-1]
    if num_experts % mesh.shape[axis]:
        raise ValueError("num_experts %d not divisible by mesh axis %r=%d"
                         % (num_experts, axis, mesh.shape[axis]))
    capacity = max(1, int(capacity_factor * n * min(k, 2) / num_experts))

    logits = x @ gate_w
    dispatch, combine, aux = top_k_gating(logits, num_experts, capacity, k=k)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), expert_params)
    fn = shard_map(
        functools.partial(_moe_local, expert_fn=expert_fn, axis=axis),
        mesh=mesh,
        in_specs=(pspec, P(None, axis, None), P(None, axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(expert_params, dispatch.astype(x.dtype),
             combine.astype(x.dtype), x)
    return out, aux


# ---------------------------------------------------------------------------
# drop-free routing: sorted rows, grouped products (single device)
# ---------------------------------------------------------------------------

def top_k_routing(router_logits, k):
    """The ``k`` largest of each token's E router logits and a softmax
    over those ``k`` alone (weights sum to 1): ``(experts (n, k) int32,
    weights (n, k) float32)``, largest first."""
    top, idx = jax.lax.top_k(router_logits.astype(jnp.float32), k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def group_limited_routing(router_logits, bias, k, n_groups, topk_groups,
                          scale):
    """DeepSeek-V3's auxiliary-loss-free routing (arXiv:2412.19437 sec.
    2.1.2; ``topk_method`` ``noaux_tc``, ``scoring_func`` ``sigmoid``):
    scores ``s = sigmoid(logits)`` in float32; the CHOICE is made on ``s +
    bias`` — the E experts are ``n_groups`` runs of consecutive experts, a
    group scores the sum of its two largest, the ``topk_groups`` best
    groups stay and the ``k`` largest among their experts are chosen —
    and the weights are the chosen experts' ``s`` (without the bias) over
    their sum, times ``scale``. Returns ``(experts (n, k) int32, weights
    (n, k) float32)``; ties go to the lower index (``lax.top_k``)."""
    s = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    choice = s + bias.astype(jnp.float32)
    n, n_exp = s.shape
    if n_groups > 1:
        groups = choice.reshape(n, n_groups, n_exp // n_groups)
        group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_groups)       # (n, tg)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_groups)[None, None],
                       axis=1)                                  # (n, G)
        choice = jnp.where(jnp.repeat(keep, n_exp // n_groups, axis=1),
                           choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def dispatch_block_rows(assignments, num_experts):
    """Rows of one tile of the grouped product: the power of two at or
    above an expert's mean share, between 16 (a packed bf16 sublane
    tile; a decode step's experts see one to a few rows) and 128 (the
    MXU's edge; a long prefill's see hundreds)."""
    mean = -(-int(assignments) // int(num_experts))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def sorted_dispatch(experts, num_experts, block_rows, first=0):
    """Lay ``n * k`` assignments out as rows sorted by expert, every
    expert's group padded to a multiple of ``block_rows``.

    experts : (n, k) int32. The layout is of the ``num_experts`` experts
    ``first ... first + num_experts - 1`` (all of a model's, or the ones
    a device holds); an assignment to any other expert takes no row.
    Returns ``(src (rows,), dest (n, k), group_sizes (E,), counts
    (E,))``: row ``r`` of the layout holds token ``src[r]`` (padding rows
    hold token 0 and are read by nobody), assignment ``(t, j)`` sits at
    row ``dest[t, j]`` (-1: its expert is not here), ``group_sizes`` are
    the padded and ``counts`` the true sizes. ``rows`` is static: ``n *
    k`` plus at most ``block_rows - 1`` a group, whatever the routing —
    nothing is ever dropped."""
    n, k = experts.shape
    flat = experts.reshape(-1) - first
    here = jnp.logical_and(flat >= 0, flat < num_experts)
    flat = jnp.where(here, flat, num_experts)   # absent: sorted last
    counts = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(1)
    padded = (counts + block_rows - 1) // block_rows * block_rows
    order = jnp.argsort(flat, stable=True)      # assignments by expert
    by_expert = flat[order]
    rank = jnp.arange(n * k, dtype=jnp.int32) \
        - (jnp.cumsum(counts) - counts)[by_expert]
    rows = (n * k + num_experts * (block_rows - 1)) \
        // block_rows * block_rows
    dest = jnp.zeros((n * k,), jnp.int32).at[order].set(
        (jnp.cumsum(padded) - padded)[by_expert] + rank)
    src = jnp.zeros((rows,), jnp.int32).at[
        jnp.where(here, dest, rows)].set(
            jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    return (src, jnp.where(here, dest, -1).reshape(n, k),
            padded[:num_experts], counts[:num_experts])


# bytes of sorted rows one grouped product takes in (it gives as many
# back): a longer call (a prefill of 8 k tokens and more) goes through in
# chunks of a power of two of tokens, one after the other, so that its
# rows — top-k a token — never exceed a third of a gigabyte in and out.
# 4096 tokens of six rows at a width of 2560 in bf16; 1024 of eight at
# 7168
MAX_ROUTED_ROW_BYTES = 4096 * 6 * 2560 * 2


def max_routed_tokens(k, d, itemsize):
    """Tokens of one grouped product under ``MAX_ROUTED_ROW_BYTES``."""
    return 1 << ((MAX_ROUTED_ROW_BYTES // (k * d * itemsize)).bit_length()
                 - 1)


def moe_ffn_sorted(x, router_logits, wg, wu, wd, k, lead=(), route=None,
                   first=0, act="relu"):
    """Drop-free top-k mixture of gated experts on one device.

    x : (n, d) tokens (the FFN's normalised input); router_logits :
    (n, E); wg, wu : (E_here, d, f); wd : (E_here, f, d), or a model's
    stacks of them with ``lead`` the static (stage, layer) of this call
    (the grouped product then reads the stack in place). ``route`` maps
    the logits to ``(experts (n, k), weights (n, k))`` (default
    :func:`top_k_routing` of ``k``); ``act`` is the experts' gate ("relu"
    | "silu"). The maps are those of the experts ``first ... first +
    E_here - 1`` of the router's E: all of them, or the share this device
    holds, whose part of the sum is what comes back. Returns ``(out (n,
    d), experts (k, n), active)``: ``out[t] = sum_j w[t, j] *
    expert_{experts[j, t]}(x[t])`` over the chosen experts that are here
    (the choice is the MAJOR axis of ``experts`` too: it is kept, and
    ``(n, k)`` pads k to a lane tile of 128); ``active`` counts the
    experts that received a row, a chunk at a time (what the grouped
    products had to read of the weights)."""
    from ..ops.pallas.moe_ffn import f_tile, moe_grouped_ffn
    n_here, d, f = wg.shape[len(lead):]
    if route is None:
        route = functools.partial(top_k_routing, k=k)
    # with f tiled a row tile re-reads its expert: the largest tile
    tiled = f_tile(d, f, wg.dtype.itemsize) < f

    def routed(x, router_logits):
        n = x.shape[0]
        experts, w = route(router_logits)
        block_rows = 128 if tiled else dispatch_block_rows(n * k, n_here)
        src, dest, group_sizes, counts = sorted_dispatch(
            experts, n_here, block_rows, first)
        y = moe_grouped_ffn(x[src], group_sizes, wg, wu, wd, block_rows,
                            lead=lead, act=act)
        # weighted sum in float32, the choice as the MAJOR axis ((n, k,
        # d) would pad k to a tile of 8); an assignment with no row adds
        # nothing (and reads no row: past the last group the kernel
        # writes none)
        dest = dest.T
        out = jnp.sum(jnp.where(
            (dest >= 0)[:, :, None],
            w.T[:, :, None] * y[jnp.maximum(dest, 0)].astype(jnp.float32),
            0.0), axis=0)
        return out.astype(x.dtype), experts.T, jnp.sum(counts > 0)

    n = x.shape[0]
    chunk = max_routed_tokens(k, d, x.dtype.itemsize)
    if n <= chunk or n % chunk:
        return routed(x, router_logits)
    out, experts, active = jax.lax.map(
        lambda part: routed(*part),
        (x.reshape(-1, chunk, d),
         router_logits.reshape(-1, chunk, router_logits.shape[-1])))
    return (out.reshape(n, d), experts.transpose(1, 0, 2).reshape(k, n),
            jnp.sum(active))
