"""Grouped expert FFN as a Pallas TPU kernel.

The sorted, drop-free half of a mixture-of-experts layer
(parallel/moe.py routes and combines): every row already sits beside
the other rows of its expert, and the kernel applies each expert's
gated MLP to its own rows,

    y[r] = ( act(x[r] Wg[e]) * (x[r] Wu[e]) ) Wd[e]      e = expert of r

with ``act`` the gate's activation, ReLU (ReGLU) or SiLU (SwiGLU).

Layout contract (what ``moe.sorted_dispatch`` builds): rows are grouped
by expert in expert order and every group is padded to a multiple of
``block_rows``, so a tile of ``block_rows`` rows belongs to exactly ONE
expert. The grid walks the tiles; the tile -> expert map is scalar-
prefetched and picks the three weight blocks. Where an expert's three
maps fit VMEM double-buffered (:func:`f_tile`: within ``_WEIGHT_VMEM``)
each block is a WHOLE expert ``(h, f)`` / ``(f, h)``: consecutive tiles
of one expert keep their block index, so the pipeline fetches an
expert's weights once however many rows it has, and never fetches an
expert with no row: a decode step streams exactly the experts it touched
(bound by HBM), a prefill re-uses each expert over hundreds of tiles
(bound by the MXU). Tiles past the last group repeat the last block
index and skip the body.

Where an expert does NOT fit (3 x 7168 x 2048 in bf16 is 88 MB, 176 MB
double-buffered, against the core's 128 MiB) the same kernel tiles ``f``:
an inner grid axis walks column blocks ``(h, tf)`` of the gate and up
maps and the matching row block ``(tf, h)`` of the down map, and a
float32 scratch accumulates the row tile's output over them (the gated
product is elementwise in ``f``, so the blocks are independent). The
whole-expert path is that kernel with one block and no scratch. With
``f`` tiled a row tile re-reads its expert's maps, so its callers give
it the largest row tile (``moe.dispatch_block_rows``): an expert's rows
then fit one tile in a decode step and its maps are still read once.

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

__all__ = ["moe_grouped_ffn", "f_tile"]

PALLAS_KERNELS = {
    "moe_grouped_ffn": "_moe_grouped_ffn_xla",
}


# VMEM the three weight blocks may claim, double-buffered: three eighths
# of a v5e core's 128 MiB (the row tiles, the accumulator and the
# compiler's own stack share the rest)
_WEIGHT_VMEM = 48 << 20
_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def f_tile(h, f, itemsize):
    """Columns of ``f`` in one weight block: all of them where an
    expert's three maps fit ``_WEIGHT_VMEM`` double-buffered, else the
    largest multiple of 128 lanes that divides ``f`` and does."""
    fits = lambda tf: 2 * 3 * h * tf * itemsize <= _WEIGHT_VMEM
    if fits(f):
        return f
    tiles = [tf for tf in range(128, f, 128) if f % tf == 0 and fits(tf)]
    if not tiles:
        raise ValueError("no 128-multiple block of f=%d (h=%d) fits %d "
                         "bytes of VMEM" % (f, h, _WEIGHT_VMEM))
    return tiles[-1]


def _moe_grouped_ffn_xla(x, group_sizes, wg, wu, wd, lead=(), act="relu"):
    """Pure-lax twin (the CPU tier-1 path): three ``ragged_dot``s over
    the same groups. Rows past the last group come out zero."""
    dt = x.dtype
    wg, wu, wd = wg[lead], wu[lead], wd[lead]
    g = jax.lax.ragged_dot(x, wg, group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, wu, group_sizes,
                           preferred_element_type=jnp.float32)
    a = (_ACTS[act](g) * u).astype(dt)
    return jax.lax.ragged_dot(a, wd, group_sizes,
                              preferred_element_type=jnp.float32).astype(dt)


def _ffn_kernel(te_ref, nv_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                *acc, act, n_f):
    """One row tile against one block of ``f`` columns of its expert;
    ``n_f`` blocks make the expert (``acc``: the float32 accumulator,
    there only where ``n_f`` > 1)."""
    j = pl.program_id(1)

    @pl.when(pl.program_id(0) < nv_ref[0])
    def _body():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        a = (_ACTS[act](g) * u).astype(x.dtype)
        y = jnp.dot(a, wd_ref[...], preferred_element_type=jnp.float32)
        if n_f == 1:
            o_ref[...] = y.astype(o_ref.dtype)
            return
        acc_ref = acc[0]

        @pl.when(j == 0)
        def _first():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _more():
            acc_ref[...] += y

        @pl.when(j == n_f - 1)
        def _last():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "lead", "act", "tf"))
def _moe_grouped_ffn(x, group_sizes, wg, wu, wd, block_rows, interpret,
                     lead=(), act="relu", tf=None):
    n, h = x.shape
    n_exp, _, f = wg.shape[len(lead):]
    n_tiles = n // block_rows
    itemsize = wg.dtype.itemsize
    tf = tf or f_tile(h, f, itemsize)
    n_f = f // tf
    # tile -> expert: the groups are whole tiles, so tile i belongs to
    # the first expert whose (padded) rows end past the tile's start
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    n_valid = ends[-1] // block_rows
    tile = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                       jnp.maximum(n_valid - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile * block_rows, side="right"),
        n_exp - 1).astype(jnp.int32)

    def row_map(i, j, te, nv):
        return (i, 0)

    def held(i, j, nv):
        # a tile past the last group keeps the last block it saw
        return jnp.where(i < nv[0], j, n_f - 1)

    def in_map(i, j, te, nv):
        return lead + (te[i], 0, held(i, j, nv))

    def out_map(i, j, te, nv):
        return lead + (te[i], held(i, j, nv), 0)

    squeezed = (None,) * (len(lead) + 1)     # stack dims and the expert

    # the three weight blocks, double-buffered, + the row tiles, the f32
    # intermediates and the accumulator; the v5e core has 128 MiB of VMEM
    # and a 16 MiB default scoped limit, which two experts of 3 x 3.9 MB
    # pass and three 22 MB blocks of a 7168 x 2048 expert do not
    vmem = (2 * 3 * h * tf * itemsize
            + block_rows * (4 * h * x.dtype.itemsize + 4 * (2 * tf + h)
                            + (4 * h if n_f > 1 else 0))
            + (8 << 20))
    return pl.pallas_call(
        functools.partial(_ffn_kernel, act=act, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, n_f),
            in_specs=[
                pl.BlockSpec((block_rows, h), row_map),
                pl.BlockSpec(squeezed + (h, tf), in_map),
                pl.BlockSpec(squeezed + (h, tf), in_map),
                pl.BlockSpec(squeezed + (tf, h), out_map),
            ],
            out_specs=pl.BlockSpec((block_rows, h), row_map),
            scratch_shapes=([pltpu.VMEM((block_rows, h), jnp.float32)]
                            if n_f > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(tile_expert, n_valid.reshape(1), x, wg, wu, wd)


def moe_grouped_ffn(x, group_sizes, wg, wu, wd, block_rows,
                    interpret=None, lead=(), act="relu", tf=None):
    """Gated expert MLPs (ReGLU, or SwiGLU with ``act="silu"``) over rows
    grouped by expert.

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
    act : "relu" | "silu", the gate's activation.
    tf : columns of ``f`` in a weight block (default :func:`f_tile`: all
        of ``f`` where an expert fits VMEM; a test forces the tiling).

    Returns (rows, h): ``(act(x wg[e]) * (x wu[e])) wd[e]`` for the
    rows of every group (the padding rows of a group compute whatever
    they hold); rows past the last group are NOT written by the Mosaic
    kernel and zero in the twin — a caller reads back only the rows it
    placed. On TPU a Mosaic kernel; off-TPU the ``ragged_dot`` twin;
    ``interpret=True`` forces the Pallas interpreter for parity tests.
    """
    if act not in _ACTS:
        raise ValueError("act=%r is not one of %s" % (act, sorted(_ACTS)))
    if x.shape[0] % block_rows:
        raise ValueError("rows %d not a multiple of block_rows %d"
                         % (x.shape[0], block_rows))
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    if interpret is None:
        if not on_tpu(x):
            return _moe_grouped_ffn_xla(x, group_sizes, wg, wu, wd,
                                        tuple(lead), act)
        interpret = False
    return _moe_grouped_ffn(x, group_sizes, wg, wu, wd, int(block_rows),
                            bool(interpret), tuple(lead), act,
                            None if tf is None else int(tf))
