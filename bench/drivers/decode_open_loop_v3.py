"""Driver ``decode_open_loop_v3``: ``decode_open_loop_v2``'s open loop — its
schedule, its ``run`` (the engine, the loop, the drain, the checks, the
result line) and its ``make_params`` — for a configuration WITHOUT experts,
which the v2 driver's comparison cannot judge (it reads the routing of
every request). It differs in:

* ``correct`` compares the logits alone: over ``reference_requests``
  short and ``reference_long_requests`` long finished requests the
  reference's largest logit less the chosen token's, a position, its
  LARGEST and its MEAN against the traffic file's two limits; a traced run
  reads each of ``reference_controls`` the same way and prints which limit
  refuses it (they decide nothing);
* the weights are v2's draws with the reference's ``fixed_leaves`` set
  (the leaves a model SETS rather than draws, such as Mamba's A_log);
* a traced run splits the device time of the kernels named in
  ``KERNEL_WORK`` (the Mamba step and scan, both differential attention
  kernels) between decode steps and prefills;
* ``aot_check`` sizes the pools THROUGH THE ENGINE (``_pool_sizes``:
  pages of each kind and its state rows, shaped under ``jax.eval_shape``),
  whatever kinds of state the model keeps, and its tables through
  ``_tables``.

v2's module is loaded under a name of its own, so that what is set on it
here is this driver's alone.
"""
import importlib.util
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load_v2():
    name = "bench_decode_open_loop_v2_for_v3"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, "decode_open_loop_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_v2 = _load_v2()
schedule, model_config = _v2.schedule, _v2.model_config
_v2_make_params = _v2.make_params

# bench/work/<name>.py of the kernels whose time a traced run splits
KERNEL_WORK = ("mamba_recurrent_step", "mamba_chunk_scan",
               "diff_paged_decode", "diff_flash_prefill")


def make_params(reference, config, seed, device):
    """v2's draw, with the reference's fixed leaves set on ``device``."""
    import jax
    params = _v2_make_params(reference, config, seed, device)
    with jax.default_device(device):
        return jax.block_until_ready(
            reference.with_fixed(params, config["model"]))


def _readings(ref, params, model, picks, cap, _out_max, weights_as=None):
    """The reference over each picked request's whole sequence (padded to
    ``cap``: one compiled program) against the tokens the timed engine
    generated: the gap of each (the reference's largest logit less the
    chosen token's, a position; ``reference.token_gaps``)."""
    gaps = np.concatenate([np.asarray(ref.token_gaps(
        params, np.asarray(r["prompt"] + r["tokens"], np.int32), model,
        len(r["prompt"]), pad_to=cap, weights_as=weights_as))
        for r in picks])
    return {"tokens": gaps.size, "gap_max": float(gaps.max()),
            "gap_mean": float(gaps.mean()),
            "argmax_share": float(np.mean(gaps == 0.0)),
            "prompts": [len(r["prompt"]) for r in picks]}


def _judge(reading, tr):
    """The comparison's one check, against the traffic file's limits."""
    tol = float(tr["reference_logit_tolerance"])
    tol_mean = float(tr["reference_mean_logit_gap_max"])
    return [(
        "tokens_agree_with_reference",
        reading["gap_max"] <= tol and reading["gap_mean"] <= tol_mean,
        "%d generated tokens: (max logit - chosen logit) largest %.4f "
        "(tolerance %g), mean %.5f (at most %g); the reference's own "
        "choice for %.4f of them"
        % (reading["tokens"], reading["gap_max"], tol, reading["gap_mean"],
           tol_mean, reading["argmax_share"]))]


# v2's loop (its own copy: see _load_v2) with this driver's weights,
# comparison and kernels
_v2.make_params, _v2._readings, _v2._judge, _v2.KERNEL_WORK = (
    make_params, _readings, _judge, KERNEL_WORK)


def run(ctx):
    # v2 names the routing checks when too few requests finished: the
    # comparison here has the first alone
    result = _v2.run(ctx)
    result["checks"] = [c for c in result["checks"]
                        if "routing" not in c[0]]
    return result


def aot_check(cell, hbm, aot):
    """``bench/aot_check.py``: the largest prefill bucket and the largest
    slot bucket at the configuration's pools, compiled for a described
    v5e. The engine sizes its pools itself (``_pool_sizes``, shaped under
    ``jax.eval_shape``: nothing is allocated), so every kind of state it
    keeps is sized as a run sizes it. Weights and pools are arguments of
    both programs, so the live bytes printed are what the chip must hold
    while one runs."""
    import jax
    from mxnet_tpu.parallel.transformer import init_kv_pages
    from mxnet_tpu.serve.decode import DecodeConfig, DecodeEngine
    one = jax.sharding.SingleDeviceSharding(aot.describe().devices[0])
    cfg = model_config(cell.config)
    eng = cell.config["engine"]
    tree = cell.reference().param_tree(cell.config["model"])
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], cfg.dtype, sharding=one),
        tree, is_leaf=_v2._is_spec)

    def engine_config(num_pages, window_pages):
        return DecodeConfig(
            slots=eng["slots"], page_size=eng["page_size"],
            num_pages=num_pages, window_pages=window_pages,
            max_context=eng["max_context"], queue_depth=eng["queue_depth"])

    # an engine of two-page pools to build the programs; the sizes of the
    # pools it WOULD make at the configuration's (the engine's own
    # _pool_sizes: pages of each kind, its state rows) shaped, not made
    engine = DecodeEngine(params, cfg, engine_config(
        2, 2 if eng.get("window_pages") else None))
    tiny, engine._cfg = engine._cfg, engine_config(
        eng["num_pages"], eng.get("window_pages"))
    pages, dcfg, engine._cfg = engine._pool_sizes(), engine._cfg, tiny
    k_pool, v_pool = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: init_kv_pages(cfg, pages, eng["page_size"])))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=one)
    nbytes = lambda t: sum(int(np.prod(p.shape)) * p.dtype.itemsize
                           for p in jax.tree_util.tree_leaves(t))
    print("weights %.3f GB; pools (k and v, or state and tails) %s GB"
          % (nbytes(params) / aot.GB,
             " + ".join("%.3f" % ((nbytes(k) + nbytes(v)) / aot.GB)
                        for k, v in zip(jax.tree_util.tree_leaves(k_pool),
                                        jax.tree_util.tree_leaves(v_pool)))))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"     # on_tpu(): the Mosaic kernels
    try:
        bucket = dcfg.prefill_buckets[-1]
        n_pb = bucket // dcfg.page_size
        prefill = engine._prefill_prog(bucket).lower(
            params, k_pool, v_pool,
            engine._tables(i32(n_pb), i32(engine._ring_pages or 0), i32()),
            i32(1, bucket), i32(1)).compile()
        slots = dcfg.slot_buckets[-1]
        step = engine._step_prog(slots).lower(
            params, k_pool, v_pool,
            engine._tables(i32(slots, dcfg.pages_per_seq),
                           i32(slots, engine._ring_pages or 0), i32(slots)),
            i32(slots), i32(slots)).compile()
    finally:
        jax.default_backend = real_backend
    for name, prog in (("prefill", prefill), ("step", step)):
        print("%s: %d Mosaic custom calls"
              % (name, prog.as_text().count("tpu_custom_call")))
    return max(aot.report("decode_prefill[%d]" % bucket, prefill, hbm),
               aot.report("decode_step[%d]" % slots, step, hbm))

