"""Grouped expert FFN as a Pallas TPU kernel.

The sorted, drop-free half of a mixture-of-experts layer
(parallel/moe.py routes and combines): every row already sits beside
the other rows of its expert, and the kernel applies each expert's
gated MLP to its own rows,

    y[r] = ( relu(x[r] Wg[e]) * (x[r] Wu[e]) ) Wd[e]     e = expert of r

Layout contract (what ``moe.sorted_dispatch`` builds): rows are grouped
by expert in expert order and every group is padded to a multiple of
``block_rows``, so a tile of ``block_rows`` rows belongs to exactly ONE
expert. The grid walks the tiles; the tile -> expert map is scalar-
prefetched and picks the three weight blocks, each a WHOLE expert
``(h, f)`` / ``(f, h)`` resident in VMEM. Consecutive tiles of one
expert keep their block index, so the pipeline fetches an expert's
weights once however many rows it has, and never fetches an expert
with no row: a decode step streams exactly the experts it touched
(bound by HBM), a prefill re-uses each expert over hundreds of tiles
(bound by the MXU). Tiles past the last group repeat the last block
index and skip the body.

The weights may be a model's whole STACK ``(stages, layers, E, h, f)``
with the layer named by ``lead``: the block index picks the layer, so no
``stack[stage, layer]`` slice — which XLA materialises as a copy of all
the layer's experts in front of a custom call, 0.7 GB a layer at the
served sizes — is ever made.

Forward-only (serving); no VJP is defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import on_tpu

__all__ = ["moe_grouped_ffn"]

PALLAS_KERNELS = {
    "moe_grouped_ffn": "_moe_grouped_ffn_xla",
}


def _moe_grouped_ffn_xla(x, group_sizes, wg, wu, wd, lead=()):
    """Pure-lax twin (the CPU tier-1 path): three ``ragged_dot``s over
    the same groups. Rows past the last group come out zero."""
    dt = x.dtype
    wg, wu, wd = wg[lead], wu[lead], wd[lead]
    g = jax.lax.ragged_dot(x, wg, group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, wu, group_sizes,
                           preferred_element_type=jnp.float32)
    a = (jax.nn.relu(g) * u).astype(dt)
    return jax.lax.ragged_dot(a, wd, group_sizes,
                              preferred_element_type=jnp.float32).astype(dt)


def _ffn_kernel(te_ref, nv_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when(pl.program_id(0) < nv_ref[0])
    def _body():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        a = (jax.nn.relu(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            a, wd_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "lead"))
def _moe_grouped_ffn(x, group_sizes, wg, wu, wd, block_rows, interpret,
                     lead=()):
    n, h = x.shape
    n_exp, _, f = wg.shape[len(lead):]
    n_tiles = n // block_rows
    # tile -> expert: the groups are whole tiles, so tile i belongs to
    # the first expert whose (padded) rows end past the tile's start
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    n_valid = ends[-1] // block_rows
    tile = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                       jnp.maximum(n_valid - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile * block_rows, side="right"),
        n_exp - 1).astype(jnp.int32)

    def row_map(i, te, nv):
        return (i, 0)

    def w_map(i, te, nv):
        return lead + (te[i], 0, 0)

    squeezed = (None,) * (len(lead) + 1)     # stack dims and the expert

    itemsize = wg.dtype.itemsize
    # three whole experts, double-buffered, + the row tiles and the f32
    # intermediates; the v5e core has 128 MiB of VMEM and a 16 MiB
    # default scoped limit, which two experts of 3 x 3.9 MB pass
    vmem = (2 * 3 * h * f * itemsize
            + block_rows * (4 * h * x.dtype.itemsize + 4 * (2 * f + h))
            + (8 << 20))
    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((block_rows, h), row_map),
                pl.BlockSpec(squeezed + (h, f), w_map),
                pl.BlockSpec(squeezed + (h, f), w_map),
                pl.BlockSpec(squeezed + (f, h), w_map),
            ],
            out_specs=pl.BlockSpec((block_rows, h), row_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(tile_expert, n_valid.reshape(1), x, wg, wu, wd)


def moe_grouped_ffn(x, group_sizes, wg, wu, wd, block_rows,
                    interpret=None, lead=()):
    """Gated ReLU expert MLPs (ReGLU) over rows grouped by expert.

    Parameters
    ----------
    x : (rows, h) — rows sorted by expert, each expert's group padded
        to a multiple of ``block_rows`` (``rows`` is one too).
    group_sizes : (E,) int32 — the PADDED size of each expert's group,
        in expert order; their sum is at most ``rows``.
    wg, wu : (E, h, f); wd : (E, f, h) — gate, up and down maps; or
        stacks of them, ``(..., E, h, f)``, with
    lead : the static index of this call's maps in the stack's leading
        dims, e.g. ``(stage, layer)``.

    Returns (rows, h): ``(relu(x wg[e]) * (x wu[e])) wd[e]`` for the
    rows of every group (the padding rows of a group compute whatever
    they hold); rows past the last group are NOT written by the Mosaic
    kernel and zero in the twin — a caller reads back only the rows it
    placed. On TPU a Mosaic kernel; off-TPU the ``ragged_dot`` twin;
    ``interpret=True`` forces the Pallas interpreter for parity tests.
    """
    if x.shape[0] % block_rows:
        raise ValueError("rows %d not a multiple of block_rows %d"
                         % (x.shape[0], block_rows))
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    if interpret is None:
        if not on_tpu(x):
            return _moe_grouped_ffn_xla(x, group_sizes, wg, wu, wd,
                                        tuple(lead))
        interpret = False
    return _moe_grouped_ffn(x, group_sizes, wg, wu, wd, int(block_rows),
                            bool(interpret), tuple(lead))
