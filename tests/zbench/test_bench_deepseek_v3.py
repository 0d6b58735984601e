"""The ``deepseek_v3`` configuration's benchmark files: the file holds the
published config cut as it says, the reference's tree is the program's, the
work functions add up, every reader this configuration brought returns a
number where its source is there (and nothing where it is not), and the
traced rehearsal's controls are each refused. CPU only."""
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, span_log                         # noqa: E402

NAME = "deepseek_v3.decode_ctx5k"
CELL = harness.Cell(ROOT, NAME, rehearse=True)
REAL = harness.Cell(ROOT, NAME)
DRIVER = harness.load_module(CELL.driver_file)
PEAKS = harness.load_json(os.path.join(
    ROOT, "bench", "peaks.json"))["devices"]["TPU v5 lite"]
NEW_READERS = [m["name"] for m in REAL.benchmark["per_layer"]
               if m.get("workloads") == [NAME]]


def test_the_cell_is_the_one_the_issue_names():
    assert REAL.driver_name == "decode_open_loop_v2"     # no new driver
    assert (REAL.chips, REAL.config_name, REAL.traffic_name) == (
        1, "deepseek_v3", "ctx5k_poisson_p80")
    e2e = dict((m["name"], m) for m in REAL.benchmark["end_to_end"])
    assert NAME in e2e["serve_ttft_mean_ms"]["workloads"]
    assert len(NEW_READERS) == 20
    assert len(REAL.benchmark["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in REAL.benchmark["workloads"]) == 1


def test_full_size_file_holds_the_published_config_and_its_cut():
    body, pub = REAL.config, REAL.config["published"]
    changed = [k for k in pub if k != "parameters" and body[k] != pub[k]]
    assert sorted(changed) == sorted(body["reduced"]) == sorted(
        body["reduced_how"])
    assert [(k, pub[k], body[k]) for k in body["reduced"]] == [
        ("num_hidden_layers", 61, 5), ("first_k_dense_replace", 3, 1),
        ("n_routed_experts", 256, 16), ("vocab_size", 129280, 16160),
        ("num_nextn_predict_layers", 1, 0)]
    m = body["model"]
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["d_ff_dense"],
            m["kv_lora_rank"], m["q_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["num_experts"],
            m["moe_top_k"], m["moe_n_groups"], m["moe_topk_groups"],
            m["moe_routed_scale"], m["rope_base"], m["norm_eps"],
            m["rope_scaling"], m["tie_embeddings"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["kv_lora_rank"], pub["q_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["n_group"], pub["topk_group"],
        pub["routed_scaling_factor"], pub["rope_theta"],
        pub["rms_norm_eps"], pub["rope_scaling"],
        pub["tie_word_embeddings"])
    assert m["moe_shared_width"] == pub["n_shared_experts"] \
        * pub["moe_intermediate_size"]
    assert (m["n_layers"], m["dense_layers"], m["vocab_size"],
            m["moe_local_experts"]) == (
        body["num_hidden_layers"], body["first_k_dense_replace"],
        body["vocab_size"], [0, body["n_routed_experts"]])
    assert pub["vocab_size"] == 8 * m["vocab_size"]
    assert (m["gate_act"], m["moe_router"]) == (pub["hidden_act"],
                                                pub["topk_method"])
    leaves = []
    for v in REAL.reference().param_tree(m).values():
        leaves += list(v.values()) if isinstance(v, dict) else [v]
    n = sum(int(np.prod(shape)) for shape, _kind in leaves)
    assert n == 583483392 + 4 * 937640192 + 231676928 == 4565721088
    eng = body["engine"]
    assert eng["slots"] == 64 and eng["max_context"] == 16384
    tokens = (eng["num_pages"] - 1) * eng["page_size"]
    assert 400e3 <= tokens <= 520e3                    # the latent pool
    assert 2.3e9 <= tokens * 5 * 576 * 2 <= 3.0e9
    assert "departures" in body and "FP8" in " ".join(body["departures"])


def test_reference_tree_is_the_programs_layout():
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import init_transformer_params
    cfg = DRIVER.model_config(CELL.config)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    theirs, _specs = init_transformer_params(cfg, mesh, seed=0)
    ours = DRIVER.make_params(CELL.reference(), CELL.config, 3000000019,
                              jax.devices()[0])
    sig = lambda t: jax.tree_util.tree_map(
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    assert sig(ours) == sig(theirs)
    m = CELL.config["model"]
    std = lambda name: float(np.std(np.asarray(ours["layers"][name])))
    assert abs(std("we_gate") - 0.02) < 2e-3
    assert abs(std("gate_bias") - 0.02) < 8e-3       # drawn, not zero
    assert abs(std("wq_b") - m["q_lora_rank"] ** -0.5) < 5e-3
    assert abs(std("wkv_b") - m["kv_lora_rank"] ** -0.5) < 5e-3
    assert abs(std("wkv_a") - m["d_model"] ** -0.5) < 5e-3
    # the full-size config builds the program's config too
    real = DRIVER.model_config(REAL.config)
    assert real.moe_local_experts == (0, 16) and real.kv_lora_rank == 512


def test_a_program_without_the_fields_refuses_the_cell_at_once():
    """What the parent commit does with these files: its TransformerConfig
    lacks the fields, so the driver refuses before any weight is drawn."""
    import dataclasses
    from mxnet_tpu.parallel import transformer as T
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(T.TransformerConfig)
        if f.name != "kv_lora_rank"])
    real = T.TransformerConfig
    T.TransformerConfig = old
    try:
        with pytest.raises(harness.Refused, match="kv_lora_rank"):
            DRIVER.model_config(REAL.config)
    finally:
        T.TransformerConfig = real


def test_the_schedule_holds_requests_the_reference_can_be_given():
    tr = REAL.traffic
    plan = DRIVER.schedule(tr, 45.0, 3000000019, 16160)
    p = np.array([len(x) for _d, x, _o in plan])
    o = np.array([x for _d, _p, x in plan])
    assert tr["prompt_tokens"]["min"] <= p.min() and p.max() <= 12288
    assert tr["output_tokens"]["min"] <= o.min() and o.max() <= 2048
    assert max(max(x) for _d, x, _o in plan) < 16160    # ids of the slice
    assert (p + o).max() <= REAL.config["engine"]["max_context"]
    cap, long_min = tr["reference_max_tokens"], tr["reference_long_prompt_min"]
    fits = (p + o) <= cap
    assert long_min == 4096
    assert (fits & (p >= long_min)).sum() >= 3       # a long one to compare
    assert (fits & (p < long_min)).sum() >= tr["reference_requests"] + 2
    assert abs(tr["rate_per_s"] - 0.8 * tr["knee_per_s"]) < 1e-9
    assert [c["name"] for c in tr["reference_controls"]] == [
        c["name"] for c in CELL.traffic["reference_controls"]]


def test_mla_decode_work_sits_on_the_ridge():
    work = REAL.work("mla_paged_decode")
    assert work.flops(1, 128, 576, 512) == 128 * 2 * (576 + 512)
    tokens, rows = 40 * 5000 * 5, 40 * 5
    nbytes = work.nbytes(tokens, rows, 128, 576, 512, 2)
    assert nbytes == (tokens * 576 + rows * 128 * 1088) * 2
    intensity = work.flops(tokens, 128, 576, 512) / float(tokens * 576 * 2)
    assert 241 < intensity < 243                     # 242 FLOP/B
    ridge = PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"]
    assert abs(intensity / ridge - 1) < 0.03
    both = (nbytes / PEAKS["hbm_bytes_per_s"],
            work.flops(tokens, 128, 576, 512) / PEAKS["bf16_flops_per_s"])
    assert work.roofline_seconds(tokens, rows, 128, 576, 512, 2,
                                 PEAKS) == max(both)
    assert re.match(work.TRACE_NAME, "_mla_paged_decode.7")
    assert not re.match(work.TRACE_NAME, "_mla_flash_prefill.2")


def test_model_flops_of_the_cut_model():
    work, m = REAL.work(REAL.config["work"]), REAL.config["model"]
    attention = 187107328 - 1536 - 512          # the maps, not the norms
    maps = 5 * attention + 3 * 7168 * 18432 + 4 * (7168 * 256
                                                   + 3 * 7168 * 2048)
    head = 7168 * 16160
    assert work.token_flops(m, 0) == 2 * (maps + 5 * 128 * 320 + head)
    pos = 5000
    assert work.token_flops(m, pos) - work.token_flops(m, 0) \
        == 2 * 5 * 128 * 320 * pos
    n = 3000
    assert work.prefill_flops(m, n) == sum(
        work.token_flops(m, p) for p in range(n)) - 2 * head * (n - 1)
    assert work.routed_flops(m, 10) == 10 * 6 * 7168 * 2048


class _Trace(object):
    """A reduced trace that saw 30 ms of each kernel in 12 calls."""
    busy_s, window_s, idle_share = 2.0, 3.0, 1.0 / 3

    def seconds_matching(self, pattern):
        return 0.030, 12, [pattern]


def _run(monkeypatch, trace=True):
    """What a reader sees after a traced run of the cell: the samples the
    v2 driver returns, a span log of one prefill and two steps."""
    steps = [{"name": "decode.iteration", "span_id": 1, "parent_id": None,
              "t0": 10.0, "t1": 10.5, "attrs": {"live": 1}},
             {"name": "decode.prefill", "span_id": 2, "parent_id": 1,
              "t0": 10.0, "t1": 10.3,
              "attrs": {"latent_context_tokens": 5 * 4000,
                        "moe_assignments": 4000 * 8 * 4, "moe_rows": 8000,
                        "moe_active_experts": 64}},
             {"name": "decode.step", "span_id": 3, "parent_id": 1,
              "t0": 10.3, "t1": 10.34,
              "attrs": {"context_tokens": 200000, "latent_rows": 200,
                        "window_context_tokens": 200000,
                        "latent_context_tokens": 1000000,
                        "moe_assignments": 40 * 8 * 4, "moe_rows": 80,
                        "moe_active_experts": 40}}]
    # a second pass: the same step again
    again = [dict(steps[0], span_id=4, t0=10.51, t1=10.56),
             dict(steps[2], span_id=5, parent_id=4, t0=10.51, t1=10.55)]
    monkeypatch.setattr(span_log, "records", lambda: steps + again)
    monkeypatch.setattr(span_log, "decode_window", lambda run: [
        (steps[0], steps[1:]), (again[0], again[1:])])
    req = types.SimpleNamespace(
        due=10.0, sent=10.0, enq=10.0, admit=10.0, first=10.3, done=12.3,
        tokens=41, prompt_len=4000, want_tokens=41, failed=False,
        error=None)
    samples = {"requests": [req], "all_requests": [req], "slots": 64,
               "window_counts": {"steps": 40, "step_seconds": 1.6,
                                 "prefills": 1, "prefill_seconds": 0.3,
                                 "tokens": 41, "requests": 1},
               "window_s": 45.0, "window_host": (9.0, 54.0),
               "kv_itemsize": 2, "pages_used": {"global": [0.25, 0.35]},
               "kernel_split": {"moe_grouped_ffn": {
                   "step_s": 0.02, "prefill_s": 0.01, "other_s": 0.0,
                   "calls": 12}},
               "trace_host_window": (10.0, 13.0) if trace else None,
               "trace_counts": {"steps": 10} if trace else None}
    return types.SimpleNamespace(
        cell=REAL, config=REAL.config, traffic=REAL.traffic, chips=1,
        peaks=PEAKS, driver=REAL.driver_name, samples=samples, counters={},
        end_to_end={}, trace=_Trace() if trace else None,
        memory_peak_bytes=0, work=REAL.work)


@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_a_number(monkeypatch, name):
    mod = harness.load_module(REAL.metric_file(name))
    assert REAL.driver_name in mod.DRIVERS
    value = mod.read(_run(monkeypatch))
    assert value is not None and np.isfinite(value) and value >= 0, name
    if name.endswith("roofline") or "roofline" in name or "mfu" in name:
        assert 0 < value < 100, (name, value)


def test_kernel_readers_do_the_arithmetic_they_say(monkeypatch):
    read = lambda n, **kw: harness.load_module(REAL.metric_file(n)).read(
        _run(monkeypatch, **kw))
    assert abs(read("mla.decode_attn_ms_per_step") - 3.0) < 1e-9
    work = REAL.work("mla_paged_decode")
    need = work.roofline_seconds(1000000, 200, 128, 576, 512, 2, PEAKS)
    assert abs(read("mla.decode_attn_roofline")
               - 100 * 2 * need / 0.030) < 1e-9
    assert abs(read("moe.expert_ffn_ms_per_step.mla") - 2.0) < 1e-9
    assert abs(read("moe.local_assignment_share")
               - 100.0 * 8160 / 130560) < 1e-9
    assert read("moe.active_experts_per_layer_step.mla") == 10.0
    assert abs(read("decode.kv_pool_used_share.latent") - 30.0) < 1e-9
    flops = 2 * 128 * 320 * 5 * (4000 * 4001 // 2)
    assert abs(read("mla.prefill_attn_roofline")
               - 100.0 * flops / PEAKS["bf16_flops_per_s"] / 0.030) < 1e-6
    # ... and nothing where there is no trace, or no such spans
    for name in ("mla.decode_attn_ms_per_step", "mla.decode_attn_roofline",
                 "mla.prefill_attn_roofline"):
        assert read(name, trace=False) is None
    monkeypatch.setattr(span_log, "records", lambda: [])
    mod = harness.load_module(REAL.metric_file("mla.decode_attn_roofline"))
    run = _run(monkeypatch)
    monkeypatch.setattr(span_log, "records", lambda: [])
    assert mod.read(run) is None


def test_the_latent_pool_reader_is_silent_for_other_models(monkeypatch):
    run = _run(monkeypatch)
    run.config = harness.Cell(
        ROOT, "smallthinker_21b_a3b.decode_mixed_len").config
    mod = harness.load_module(REAL.metric_file(
        "decode.kv_pool_used_share.latent"))
    assert mod.read(run) is None
    mfu = harness.load_module(REAL.metric_file("decode.step_mfu.mla"))
    run.work = harness.Cell(
        ROOT, "smallthinker_21b_a3b.decode_mixed_len").work
    assert mfu.read(run) is None


def test_every_control_of_the_rehearsal_is_refused_by_a_limit():
    """The traced rehearsal reads the comparison against each control —
    the reference in float8, without the group limit, without mscale^2 on
    the softmax scale — from the TIMED programs' tokens and routing: each
    must be refused, and the sound reading not; the result line carries
    the readers that need no device trace."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", NAME, "--seed", "2147489120", "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = [ln for ln in proc.stdout.splitlines() if "] control " in ln]
    names = [c["name"] for c in CELL.traffic["reference_controls"]]
    assert names == ["float8_weights", "no_group_limit",
                     "softmax_scale_without_mscale_squared"]
    assert len(said) == len(names)
    for name, ln in zip(names, said):
        assert "control %s: refused by " % name in ln, ln
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    assert {"moe.local_assignment_share", "decode.kv_pool_used_share.latent",
            "moe.active_experts_per_layer_step.mla", "serve.tpot_p95_ms.mla",
            "decode.step_ms_mean.mla", "decode.slot_occupancy.mla",
            "decode.host_ms_per_step.mla"} <= got <= set(NEW_READERS)
    share = line["metrics"]["moe.local_assignment_share"]["value"]
    assert 5.0 < share < 60.0           # 4 of 16 held, in one of 4 groups
