#!/usr/bin/env python
"""Compile every exported Pallas kernel with Mosaic for a TPU v5e —
WITHOUT a chip.

libtpu can describe a topology it is not attached to
(``jax.experimental.topologies``), and ``jit(...).lower(...).compile()``
against that description runs the real Mosaic/XLA:TPU compiler. So what
Mosaic refuses (block shapes, DMA slices, VMEM budget, unsupported
layouts) shows here, for free, before chip time is spent:

    JAX_PLATFORMS=cpu python tools/check_mosaic_aot.py [name part ...]

Nothing is executed: this proves a kernel COMPILES at these shapes, not
that it is right or fast — ``python chip_smoke.py`` on the chip compares
every kernel with its lax twin. Exits non-zero when any case fails.
"""
import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.experimental import topologies                       # noqa: E402

fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
i8 = importlib.import_module("mxnet_tpu.ops.pallas.int8_matmul")
mf = importlib.import_module("mxnet_tpu.ops.pallas.moe_ffn")
ml = importlib.import_module("mxnet_tpu.ops.pallas.mla_attention")
gd = importlib.import_module("mxnet_tpu.ops.pallas.gated_delta")

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


def cases():
    """(name, fn, [(shape, dtype), ...]) at the production callers'
    shapes (chip_smoke.py runs the same ones on the chip)."""
    for b, h, s, d, dt in ((8, 16, 1024, 64, F32), (8, 16, 1024, 64, BF16),
                           (2, 8, 1024, 128, BF16), (2, 4, 100, 64, F32)):
        yield ("flash_attention b%dh%ds%dd%d %s" % (b, h, s, d, dt.__name__),
               lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                  interpret=False),
               [((b, h, s, d), dt)] * 3)
    for dt in (F32, BF16):
        # (heads narrower than a lane tile: the call compiles, as the twin)
        for b, kvh, g, hd in ((8, 2, 4, 64), (8, 4, 2, 128), (4, 2, 4, 32),
                              (8, 1, 8, 64), (8, 8, 1, 128)):
            pool = ((512, 16, kvh, hd), dt)
            yield ("paged_decode_attention b%dkvh%dg%dhd%d %s"
                   % (b, kvh, g, hd, dt.__name__),
                   lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
                       q, kp, vp, bt, ln, interpret=False),
                   [((b, kvh, g, hd), dt), pool, pool, ((b, 16), I32),
                    ((b,), I32)])
        for b, s, nh, kvh, hd in ((2, 128, 8, 2, 64), (2, 256, 8, 4, 128),
                                  (2, 128, 8, 2, 32), (2, 64, 4, 1, 64),
                                  (4, 256, 16, 16, 64), (1, 512, 32, 8, 128)):
            pool = ((b * (s // 16) + 1, 16, kvh, hd), dt)
            kv = ((b, s, kvh, hd), dt)
            yield ("flash_prefill_paged b%ds%dnh%dkvh%dhd%d %s"
                   % (b, s, nh, kvh, hd, dt.__name__),
                   lambda *a: fa.flash_prefill_paged(*a, interpret=False),
                   [((b, s, nh, hd), dt), kv, kv, pool, pool,
                    ((b, s // 16), I32)])
    for m, k, n in ((32, 2048, 1000), (32 * 56 * 56, 576, 64)):
        yield ("int8_matmul %dx%dx%d" % (m, k, n),
               lambda x, w, s: i8.int8_matmul(x, w, s, interpret=False),
               [((m, k), I8), ((n, k), I8), ((n,), F32)])
    # a window layer of the two-kind cache at its served size: 32 rows on
    # rings of 257 entries, window 4096; an 8192-token prefill onto a ring
    ring_pool = ((32 * 257 + 1, 16, 4, 128), BF16)
    yield ("paged_decode_attention window4096 ring257 b32kvh4g7hd128",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, window=4096),
           [((32, 4, 7, 128), BF16), ring_pool, ring_pool, ((32, 257), I32),
            ((32,), I32)])
    kv = ((1, 8192, 4, 128), BF16)
    yield ("flash_prefill_paged window4096 ring257 s8192nh28kvh4hd128",
           lambda q, k, v, kp, vp, bt, ln: fa.flash_prefill_paged(
               q, k, v, kp, vp, bt, interpret=False, lengths=ln,
               window=4096),
           [((1, 8192, 28, 128), BF16), kv, kv, ring_pool, ring_pool,
            ((1, 257), I32), ((1,), I32)])
    # the WHOLE pools of both served cells, read and written in place at
    # a layer that is not the first: 24 layers of 3072 pages x 16 KV
    # heads under 32 slots and a 2048-token prompt; the 9 window layers'
    # pool of 4096 pages x 4 KV heads on rings of 257 entries
    whole = ((24, 3072, 16, 16, 128), BF16)
    yield ("paged_decode_attention layer23of24 b32kvh16g1hd128",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, layer=23),
           [((32, 16, 1, 128), BF16), whole, whole, ((32, 128), I32),
            ((32,), I32)])
    kv = ((1, 2048, 16, 128), BF16)
    yield ("flash_prefill_paged layer23of24 s2048nh16kvh16hd128",
           lambda q, k, v, kp, vp, bt: fa.flash_prefill_paged(
               q, k, v, kp, vp, bt, interpret=False, layer=23),
           [((1, 2048, 16, 128), BF16), kv, kv, whole, whole,
            ((1, 128), I32)])
    whole = ((9, 4096, 16, 4, 128), BF16)
    yield ("paged_decode_attention layer8of9 window4096 ring257 b32kvh4g7",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, window=4096, layer=8),
           [((32, 4, 7, 128), BF16), whole, whole, ((32, 257), I32),
            ((32,), I32)])
    # ... and its 3 global layers: 1024 table entries a row, walked 16
    # pages a block (the window layers' rings too; 8 at the first cell's
    # 64 KB pages)
    global_pool = ((3, 6144, 16, 4, 128), BF16)
    yield ("paged_decode_attention layer2of3 entries1024 b32kvh4g7hd128",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, layer=2),
           [((32, 4, 7, 128), BF16), global_pool, global_pool,
            ((32, 1024), I32), ((32,), I32)])
    # which pools the decode walk can copy pages out of: bf16 head counts
    # that fill their memory tile's rows run the kernel, the others (and
    # the narrow heads above) must still compile, as the twin
    for kvh, g in ((2, 2), (24, 1), (1, 8), (3, 2), (6, 2), (12, 1)):
        pool = ((2, 300, 16, kvh, 128), BF16)
        yield ("paged_decode_attention b8kvh%dg%dhd128 bfloat16 %s"
               % (kvh, g, "kernel" if fa._pages_can_be_copied(kvh, 128, 2)
                  else "twin"),
               lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
                   q, kp, vp, bt, ln, interpret=False, layer=1),
               [((8, kvh, g, 128), BF16), pool, pool, ((8, 64), I32),
                ((8,), I32)])
    kv = ((1, 16384, 4, 128), BF16)
    yield ("flash_prefill_paged layer8of9 window4096 ring257 s16384nh28kvh4",
           lambda q, k, v, kp, vp, bt, ln: fa.flash_prefill_paged(
               q, k, v, kp, vp, bt, interpret=False, lengths=ln,
               window=4096, layer=8),
           [((1, 16384, 28, 128), BF16), kv, kv, whole, whole,
            ((1, 257), I32), ((1,), I32)])
    # a head narrower than a lane tile: the kernel attends, XLA scatters
    # into the layer in place
    narrow = ((3, 33, 16, 2, 64), F32)
    kv = ((2, 128, 2, 64), F32)
    yield ("flash_prefill_paged layer1of3 s128nh8kvh2hd64 scatter",
           lambda q, k, v, kp, vp, bt: fa.flash_prefill_paged(
               q, k, v, kp, vp, bt, interpret=False, layer=1),
           [((2, 128, 8, 64), F32), kv, kv, narrow, narrow, ((2, 8), I32)])
    # 64 experts of 2560 x 768: a 32-slot decode step's 192 assignments
    # in tiles of 16, a 16384-token prefill's 98304 in tiles of 128
    # (layer 1 of a stack of two, read in place)
    for rows, tile in ((1152, 16), (106368, 128)):
        w_in, w_out = ((1, 2, 64, 2560, 768), BF16), ((1, 2, 64, 768, 2560),
                                                      BF16)
        yield ("moe_grouped_ffn rows%d tile%d" % (rows, tile),
               lambda x, gs, wg, wu, wd, t=tile: mf.moe_grouped_ffn(
                   x, gs, wg, wu, wd, t, interpret=False, lead=(0, 1)),
               [((rows, 2560), BF16), ((64,), I32), w_in, w_in, w_out])
    # 16 held experts of 7168 x 2048 (88 MB each: f is tiled in blocks of
    # 512 columns), SiLU gate: a 64-slot decode step's and a 1024-token
    # prefill chunk's assignments in tiles of 128 (layer 3 of a stack of 4)
    for rows in (2432, 10112):
        w_in, w_out = ((1, 4, 16, 7168, 2048), BF16), ((1, 4, 16, 2048, 7168),
                                                       BF16)
        yield ("moe_grouped_ffn f-tiled silu rows%d tile128" % rows,
               lambda x, gs, wg, wu, wd: mf.moe_grouped_ffn(
                   x, gs, wg, wu, wd, 128, interpret=False, lead=(0, 3),
                   act="silu"),
               [((rows, 7168), BF16), ((16,), I32), w_in, w_in, w_out])
    # latent attention at the served widths: 64 rows of 128 heads over a
    # whole pool of 5 layers x 897 pages of 4 lane tiles (576, 128) (absorbed,
    # layer 4); a 16384-token prompt's 32 heads (a group) decompressed
    latent_pool = ((5, 897, 4, 576, 128), BF16)
    yield ("mla_paged_decode layer4of5 b64h128 rank512+64 page512",
           lambda q, pages, bt, ln: ml.mla_paged_decode(
               q, pages, bt, ln, 0.135, 512, interpret=False, layer=4),
           [((64, 128, 576), BF16), latent_pool, ((64, 32), I32),
            ((64,), I32)])
    for s, heads in ((16384, 32), (512, 128)):
        yield ("mla_flash_prefill s%dh%d nope128 rope64 v128" % (s, heads),
               lambda qn, qr, kn, kr, v: ml.mla_flash_prefill(
                   qn, qr, kn, kr, v, 0.135, interpret=False),
               [((1, heads, s, 128), BF16), ((1, heads, s, 64), BF16),
                ((1, heads, s, 128), BF16), ((1, s, 64), BF16),
                ((1, heads, s, 128), BF16)])
    # Gated DeltaNet at the served widths: 128 rows of 32 value heads of
    # (128, 128) states in a whole pool of 9 layers x 129 rows (layer 8,
    # in place); a 16384-token prompt's 16 key and 32 value heads in
    # chunks of 64, and one whose length is no multiple of the chunk
    state_pool = ((9, 129, 32, 128, 128), F32)
    yield ("gdn_recurrent_step layer8of9 b128 kh16 vh32 d128",
           lambda q, k, v, g, b, pool, rows: gd.gdn_recurrent_step(
               q, k, v, g, b, pool, rows, layer=8, interpret=False),
           [((128, 16, 128), F32), ((128, 16, 128), F32),
            ((128, 32, 128), BF16), ((128, 32), F32), ((128, 32), F32),
            state_pool, ((128,), I32)])
    for s in (16384, 200):
        yield ("gdn_chunk_prefill s%d kh16 vh32 d128 chunk64" % s,
               lambda q, k, v, g, b: gd.gdn_chunk_prefill(
                   q, k, v, g, b, interpret=False),
               [((1, s, 16, 128), F32), ((1, s, 16, 128), F32),
                ((1, s, 32, 128), BF16), ((1, s, 32), F32),
                ((1, s, 32), F32)])
    # the full layers beside them: 2 KV heads of 256 serving 8 query heads
    # each, pages of 256 tokens in a whole pool of 3 layers (layer 2)
    wide = ((3, 2345, 256, 2, 256), BF16)
    yield ("paged_decode_attention layer2of3 b128kvh2g8hd256 page256",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, layer=2),
           [((128, 2, 8, 256), BF16), wide, wide, ((128, 64), I32),
            ((128,), I32)])
    # ... and as the cell serves them, pages of 512: one page a block
    wide512 = ((3, 1173, 512, 2, 256), BF16)
    yield ("paged_decode_attention layer2of3 b128kvh2g8hd256 page512",
           lambda q, kp, vp, bt, ln: fa.paged_decode_attention(
               q, kp, vp, bt, ln, interpret=False, layer=2),
           [((128, 2, 8, 256), BF16), wide512, wide512, ((128, 32), I32),
            ((128,), I32)])
    kv = ((1, 16384, 2, 256), BF16)
    yield ("flash_prefill_paged layer2of3 s16384nh16kvh2hd256 page256",
           lambda q, k, v, kp, vp, bt, ln: fa.flash_prefill_paged(
               q, k, v, kp, vp, bt, interpret=False, lengths=ln, layer=2),
           [((1, 16384, 16, 256), BF16), kv, kv, wide, wide,
            ((1, 64), I32), ((1,), I32)])
    yield ("int8_conv_im2col b32c64 56x56 3x3",
           lambda q, w, s: i8.int8_conv_im2col(
               q, w, s, (1, 1), (1, 1), (1, 1), interpret=False),
           [((32, 64, 56, 56), I8), ((64, 64, 3, 3), I8), ((64,), F32)])


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    print("compiling for %s (no device attached)" % topo.devices[0]
          .device_kind, flush=True)
    failed = 0
    only = sys.argv[1:]
    for name, fn, specs in cases():
        if only and not any(o in name for o in only):
            continue
        args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in specs]
        try:
            jax.jit(fn).lower(*args).compile()
            print("ok    %s" % name, flush=True)
        except Exception as e:      # report every case, then fail the run
            failed += 1
            print("FAIL  %s\n      %s: %s"
                  % (name, type(e).__name__, str(e)[:1500]), flush=True)
    print("%d case(s) failed" % failed if failed
          else "every kernel compiles for the v5e")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
