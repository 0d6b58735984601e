#!/usr/bin/env python
"""Prove that the serving programs leave the KV pool where it is — WITHOUT
a chip.

XLA has no view of an array for a custom call's operand: a
``k_pages[layer]`` in front of a Mosaic kernel is a copy of that layer's
share of the pool, and a ``.at[layer].set(result)`` behind it another. The
paged kernels therefore take the pool whole with the layer's index
(``ops/pallas/flash_attention.py``). This tool compiles a ``DecodeEngine``'s
decode-step and prefill programs for a DESCRIBED TPU v5e (the
``tools/check_mosaic_aot.py`` trick) with the TPU branches taken, at a
small model whose pool dwarfs its activations, once with one pool, once
with a pool a kind of layer, once with a latent-attention model's one
latent pool (no V pool) and once with a linear-attention model's state
pools beside its full layers' pages (the recurrent kernel reads and writes
a row's states in place; the convolution tails are gathered and scattered
by XLA), and reads the compiled module:

* no instruction of the entry computation other than a Mosaic call produces
  an array of one layer's pool shape (``layer_copies``);
* both pools are aliased argument -> result (``aliased`` bytes);
* the program's temporaries are smaller than ONE layer of one pool, so
  no copy of a layer can hide under another name (``temps``).

    JAX_PLATFORMS=cpu python tools/check_pool_in_place.py

Prints one JSON line a program and exits non-zero if any fails. Nothing is
executed and no number here is a measurement.
"""
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.experimental import topologies                       # noqa: E402
from jax.sharding import Mesh                                 # noqa: E402

from mxnet_tpu.parallel.transformer import (                  # noqa: E402
    TransformerConfig, init_kv_pages, init_transformer_params)
from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine  # noqa: E402

PAGE, SLOTS, CONTEXT = 16, 4, 256
DENSE = dict(vocab_size=512, d_model=256, n_heads=2, n_layers=3, d_ff=512,
             max_len=CONTEXT, pos_type="learned", dtype=jnp.bfloat16)
HYBRID = dict(vocab_size=512, d_model=256, n_heads=4, n_kv_heads=2,
              head_dim=128, n_layers=4, d_ff=512, max_len=CONTEXT,
              pos_type="rope", norm="rmsnorm", tie_embeddings=False,
              sliding_window=64, window_layout=(0, 1, 0, 1),
              rope_layout=(0, 1, 0, 1), dtype=jnp.bfloat16)
# latent attention at the served latent width (512 + 64) and page (512):
# one pool, no V pool, written by XLA scatters and read by the kernel
LATENT = dict(vocab_size=512, d_model=256, n_heads=4, n_layers=3, d_ff=128,
              max_len=2048, pos_type="rope", norm="rmsnorm",
              tie_embeddings=False, num_experts=8, moe_top_k=2,
              moe_router="noaux_tc", moe_n_groups=2, moe_topk_groups=1,
              kv_lora_rank=512, q_lora_rank=256, qk_nope_head_dim=128,
              qk_rope_head_dim=64, v_head_dim=128, dense_layers=1,
              d_ff_dense=256, gate_act="silu", moe_shared_width=128,
              moe_local_experts=(0, 4), dtype=jnp.bfloat16)
# Gated DeltaNet layers at the served state (128 x 128 a value head) beside
# a gated full-attention layer: the second "pool" is the state rows
LINEAR = dict(vocab_size=512, d_model=256, n_heads=4, n_kv_heads=2,
              head_dim=128, n_layers=8, d_ff=128, max_len=CONTEXT,
              pos_type="rope", norm="rmsnorm", tie_embeddings=False,
              num_experts=8, moe_top_k=2, moe_router="topk",
              gate_act="silu", moe_shared_width=128, moe_shared_gate=True,
              rotary_share=0.25, qk_norm=True, attn_gate=True,
              norm_zero_centered=True, linear_layout=(1, 1, 1, 0) * 2,
              linear_key_heads=4, linear_value_heads=8, linear_key_dim=128,
              linear_value_dim=128, linear_conv_width=4, dtype=jnp.bfloat16)
# page counts no other array of the programs has a dimension of
CONFIGS = (("one_pool", DENSE, 16411, None, PAGE, CONTEXT),
           ("pool_a_kind", HYBRID, 16411, 16417, PAGE, CONTEXT),
           ("latent_pool", LATENT, 211, None, 512, 2048),
           ("state_rows", LINEAR, 16411, 1031, PAGE, CONTEXT))

# value names, result types and opcodes of an HLO text's instructions
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
# what names a buffer without making one
_VIEWS = ("parameter", "bitcast", "get-tuple-element", "tuple", "constant")


def entry_instructions(text):
    """(opcode, [result dims, ...], line) of the ENTRY computation."""
    out, inside = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            m = _INSTR.match(line)
            if m:
                dims = [tuple(int(d) for d in s.split(",") if d)
                        for s in _SHAPE.findall(m.group(1))]
                out.append((m.group(2), dims, line.strip()))
    return out


def layer_copies(text, layer_shapes):
    """Instructions that materialise one layer of a pool outside the
    Mosaic calls: anything but a view whose result, 1-dims aside, has a
    layer's shape."""
    found = []
    for op, dims, line in entry_instructions(text):
        if op in _VIEWS or op == "custom-call":
            continue
        if any(tuple(d for d in shape if d != 1) in layer_shapes
               for shape in dims):
            found.append(line[:160])
    return found


def check(name, program, compiled, pools):
    leaves = jax.tree_util.tree_leaves(pools)
    nbytes = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize
    layer_shapes = {tuple(d for d in p.shape[1:] if d != 1) for p in leaves}
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    copies = layer_copies(text, layer_shapes)
    line = {
        "config": name, "program": program,
        "mosaic_calls": text.count("tpu_custom_call"),
        "layer_copies": len(copies),
        "pool_bytes": sum(nbytes(p) for p in leaves),
        "aliased_bytes": int(ma.alias_size_in_bytes),
        "layer_bytes": min(nbytes(p) // p.shape[0] for p in leaves),
        "temp_bytes": int(ma.temp_size_in_bytes),
    }
    line["ok"] = bool(line["mosaic_calls"] > 0 and not copies
                      and line["aliased_bytes"] >= line["pool_bytes"]
                      and line["temp_bytes"] < line["layer_bytes"])
    print(json.dumps(line), flush=True)
    for c in copies[:4]:
        print("      " + c, flush=True)
    return line["ok"]


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    print("compiling for %s (no device attached)"
          % topo.devices[0].device_kind, flush=True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=one)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "sp", "tp", "pp", "ep"))
    ok = True
    # ``second``: the second pool's entries — window pages or state rows
    for name, model, pages, second, page, context in CONFIGS:
        cfg = TransformerConfig(**model)
        params = on_chip(jax.eval_shape(
            lambda: init_transformer_params(cfg, mesh, seed=0)[0]))
        # a two-page engine; its programs take the pools as arguments and
        # are lowered at the size under test (bench/aot_check.py's way)
        engine = DecodeEngine(params, cfg, DecodeConfig(
            slots=SLOTS, page_size=page, num_pages=2, max_context=context,
            window_pages=2 if "sliding_window" in model else None))
        dcfg = engine.config
        k_pool, v_pool = on_chip(jax.eval_shape(lambda: init_kv_pages(
            cfg, (pages, second) if second else pages, page)))
        bucket, slots = dcfg.prefill_buckets[-1], dcfg.slot_buckets[-1]
        real_backend = jax.default_backend
        jax.default_backend = lambda: "tpu"   # on_tpu(): the Mosaic kernels
        try:
            step = engine._step_prog(slots).lower(
                params, k_pool, v_pool,
                engine._tables(i32(slots, dcfg.pages_per_seq),
                               on_chip(engine._second_table(slots))),
                i32(slots), i32(slots)).compile()
            prefill = engine._prefill_prog(bucket).lower(
                params, k_pool, v_pool,
                engine._tables(i32(bucket // page),
                               on_chip(engine._second_table())),
                i32(1, bucket), i32(1)).compile()
        finally:
            jax.default_backend = real_backend
        ok &= check(name, "step", step, (k_pool, v_pool))
        ok &= check(name, "prefill", prefill, (k_pool, v_pool))
        engine.close(drain=False)
    print("the pools stay in place" if ok else "a pool is copied by layer")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
