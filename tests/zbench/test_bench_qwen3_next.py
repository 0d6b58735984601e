"""The ``qwen3_next_80b_a3b`` configuration's benchmark files: the file
holds the published config cut as it says, the reference's tree is the
program's, the work functions count what they say, every reader this
configuration brought returns a number where its source is there (and
nothing where it is not), and the traced rehearsal's controls are each
refused by the limits the traffic file names. CPU only."""
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

NAME = "qwen3_next_80b_a3b.decode_long_answers"
CELL = harness.Cell(ROOT, NAME, rehearse=True)
REAL = harness.Cell(ROOT, NAME)
DRIVER = harness.load_module(CELL.driver_file)
PEAKS = harness.load_json(os.path.join(
    ROOT, "bench", "peaks.json"))["devices"]["TPU v5 lite"]
NEW_READERS = [m["name"] for m in REAL.benchmark["per_layer"]
               if m.get("workloads") == [NAME]]
CONTROLS = ["float8_weights", "rotary_over_the_whole_head",
            "topk_weights_not_renormalised"]


def test_the_cell_is_the_one_the_issue_names():
    assert REAL.driver_name == "decode_open_loop_v2"     # no new driver
    assert (REAL.chips, REAL.config_name, REAL.traffic_name) == (
        1, "qwen3_next_80b_a3b", "long_answers_poisson_p80")
    e2e = dict((m["name"], m) for m in REAL.benchmark["end_to_end"])
    assert NAME in e2e["serve_ttft_mean_ms"]["workloads"]
    assert len(NEW_READERS) == 23
    assert {"gdn.recurrent_step_roofline", "gdn.chunk_prefill_roofline",
            "decode.state_rows_used_share", "decode.step_mfu.gdn",
            "kernels.paged_decode_roofline.gdn",
            "moe.expert_ffn_roofline.gdn"} <= set(NEW_READERS)
    for name in NEW_READERS:
        assert os.path.exists(REAL.metric_file(name)), name
    entry = [c for c in REAL.benchmark["configs"]
             if c["name"] == "qwen3_next_80b_a3b"][0]
    assert len(entry["source"]) <= 200 and "arXiv:2412.06464" in \
        entry["source"] and "modeling_qwen3_next.py" in entry["source"]
    assert len(REAL.entry["why"]) <= 200


def test_full_size_file_holds_the_published_config_and_its_cut():
    body, pub = REAL.config, REAL.config["published"]
    changed = [k for k in pub if k != "parameters" and body[k] != pub[k]]
    assert sorted(changed) == sorted(body["reduced"]) == sorted(
        body["reduced_how"])
    assert [(k, pub[k], body[k]) for k in body["reduced"]] == [
        ("num_hidden_layers", 48, 12), ("num_experts", 512, 64),
        ("vocab_size", 151936, 18992)]
    m = body["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"], m["moe_shared_width"], m["num_experts"],
            m["moe_top_k"], m["rope_base"], m["norm_eps"],
            m["rotary_share"], m["linear_key_heads"],
            m["linear_value_heads"], m["linear_key_dim"],
            m["linear_value_dim"], m["linear_conv_width"],
            m["tie_embeddings"], m["gate_act"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_intermediate_size"],
        pub["shared_expert_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["rope_theta"], pub["rms_norm_eps"],
        pub["partial_rotary_factor"], pub["linear_num_key_heads"],
        pub["linear_num_value_heads"], pub["linear_key_head_dim"],
        pub["linear_value_head_dim"], pub["linear_conv_kernel_dim"],
        pub["tie_word_embeddings"], pub["hidden_act"])
    assert pub["norm_topk_prob"] is True and m["moe_router"] == "topk"
    assert (m["n_layers"], m["vocab_size"], m["moe_local_experts"]) == (
        body["num_hidden_layers"], body["vocab_size"],
        [0, body["num_experts"]])
    assert pub["vocab_size"] == 8 * m["vocab_size"]
    assert pub["num_experts"] == 8 * body["num_experts"]
    # layer l is full attention iff (l + 1) % interval == 0: whole periods
    every = pub["full_attention_interval"]
    assert m["linear_layout"] == [int((l + 1) % every != 0)
                                  for l in range(m["n_layers"])]
    assert m["n_layers"] % every == 0 and pub["decoder_sparse_step"] == 1
    leaves = []
    for v in REAL.reference().param_tree(m).values():
        leaves += list(v.values()) if isinstance(v, dict) else [v]
    n = sum(int(np.prod(shape)) for shape, _kind in leaves)
    linear, full, ffn = 33718464, 27263488, 205527040
    assert n == 9 * (linear + ffn) + 3 * (full + ffn) \
        + 2 * 18992 * 2048 + 2048 == 2929374400
    eng = body["engine"]
    assert eng["slots"] == 128 and eng["max_context"] == 16384
    assert "window_pages" not in eng    # a state row a slot + the null row
    state = (eng["slots"] + 1) * 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert 2.4e9 <= state <= 2.6e9
    tokens = (eng["num_pages"] - 1) * eng["page_size"]
    assert 550e3 <= tokens <= 650e3                      # the K/V pool
    assert 3.4e9 <= tokens * 6144 <= 4.0e9
    assert "departures" in body and "multi-token" in body["departures"][0]
    assert "8 chips share each layer" in body["deployment"]


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
    lin = lambda name: np.asarray(ours["linear_layers"][name])
    assert abs(float(np.std(lin("we_gate"))) - 0.02) < 2e-3
    assert (lin("gdn_dt_bias") == 1).all()               # as published
    assert (lin("gdn_norm_g") == 1).all()                # a plain gain
    assert 1.0 < float(np.std(lin("gdn_a_log"))) < 3.0   # wide decays
    assert 0.05 < float(np.std(lin("ln1_g"))) < 0.15     # around 0: 1 + w
    assert 0.3 < float(np.std(lin("gdn_conv"))) < 0.7
    # the full-size config builds the program's config too
    real = DRIVER.model_config(REAL.config)
    assert real.moe_local_experts == (0, 64) and real.linear_layout == (
        1, 1, 1, 0) * 3 and real.rotary_share == 0.25


def test_a_program_without_the_fields_refuses_the_cell_at_once():
    """What the parent commit does with these files: its TransformerConfig
    lacks the fields, so the driver refuses before any weight is drawn."""
    import dataclasses
    from mxnet_tpu.parallel import transformer as T
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(T.TransformerConfig)
        if f.name != "linear_layout"])
    real = T.TransformerConfig
    T.TransformerConfig = old
    try:
        with pytest.raises(harness.Refused, match="linear_layout"):
            DRIVER.model_config(REAL.config)
    finally:
        T.TransformerConfig = real


def test_the_schedule_holds_requests_the_reference_can_be_given():
    tr = REAL.traffic
    assert tr["prompt_tokens"] == {"median": 2048, "sigma": 1.0,
                                   "min": 128, "max": 12288}
    assert tr["output_tokens"] == {"median": 768, "sigma": 0.6,
                                   "min": 128, "max": 2048}
    plan = DRIVER.schedule(tr, 45.0, 3000000019, 18992)
    p = np.array([len(x) for _d, x, _o in plan])
    o = np.array([x for _d, _p, x in plan])
    assert 128 <= p.min() and p.max() <= 12288
    assert 128 <= o.min() and o.max() <= 2048
    assert max(max(x) for _d, x, _o in plan) < 18992    # ids of the slice
    assert (p + o).max() <= REAL.config["engine"]["max_context"]
    cap, long_min = tr["reference_max_tokens"], tr["reference_long_prompt_min"]
    fits = (p + o) <= cap
    assert (long_min, cap) == (4096, 6144)
    assert (fits & (p >= long_min)).sum() >= 3       # a long one to compare
    assert (fits & (p < long_min)).sum() >= tr["reference_requests"] + 2
    assert abs(tr["rate_per_s"] - 0.8 * tr["knee_per_s"]) < 1e-9
    assert [c["name"] for c in tr["reference_controls"]] == CONTROLS == [
        c["name"] for c in CELL.traffic["reference_controls"]]
    for key in ("logit_gap_largest", "logit_gap_mean",
                "prefill_routing_agreement_of_the_worst_request",
                "decode_routing_agreement_of_the_worst_request",
                "which_limit_refuses_which_control"):
        assert key in tr["tolerance_readings"], key


def test_gdn_work_against_hand_counts():
    """A row a layer: 32 states of 128 x 128 float32 read and written,
    and its vectors; a prompt token a layer: the same operations and its
    vectors alone — nothing that depends on the kernel's chunk."""
    step = REAL.work("gdn_recurrent_step")
    assert step.flops(1, 32, 128, 128) == 7 * 128 * 128 * 32 == 3670016
    state = 2 * 32 * 128 * 128 * 4
    vectors = (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32) * 4
    assert step.nbytes(1, 16, 32, 128, 128) == state + vectors == 4243712
    rows = 96 * 9
    both = (rows * 4243712 / PEAKS["hbm_bytes_per_s"],
            rows * 3670016 / PEAKS["bf16_flops_per_s"])
    assert step.roofline_seconds(rows, 16, 32, 128, 128, PEAKS) == both[0] \
        == max(both)                                     # bound by HBM
    assert re.match(step.TRACE_NAME, "_gdn_recurrent.7")
    assert not re.match(step.TRACE_NAME, "_gdn_chunk.2")
    chunk = REAL.work("gdn_chunk_prefill")
    assert chunk.flops(1, 32, 128, 128) == 3670016
    assert chunk.nbytes(1, 16, 32, 128, 128, 2) == (
        2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4 == 24832
    tokens = 2048 * 9
    both = (tokens * 24832 / PEAKS["hbm_bytes_per_s"],
            tokens * 3670016 / PEAKS["bf16_flops_per_s"])
    assert chunk.roofline_seconds(tokens, 16, 32, 128, 128, 2,
                                  PEAKS) == max(both)
    assert 140 < 3670016 / 24832.0 < 150 < PEAKS["bf16_flops_per_s"] \
        / PEAKS["hbm_bytes_per_s"]
    assert re.match(chunk.TRACE_NAME, "_gdn_chunk")
    assert not re.match(chunk.TRACE_NAME, "_gdn_recurrent.1")


def test_model_flops_of_the_cut_model():
    work, m = REAL.work(REAL.config["work"]), REAL.config["model"]
    linear = (2048 * 12288 + 2048 * 64 + 4 * 8192
              + 3.5 * 32 * 128 * 128 + 4096 * 2048)
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048
    head = 2048 * 18992
    maps = 9 * linear + 3 * full + 12 * ffn
    pair = 3 * 16 * 2 * 256            # 3 full layers attend the cache
    assert work.token_flops(m, 0) == 2 * (maps + pair + head)
    pos = 5000
    assert work.token_flops(m, pos) - work.token_flops(m, 0) \
        == 2 * pair * pos
    n = 3000
    assert abs(work.prefill_flops(m, n) - (sum(
        work.token_flops(m, p) for p in range(n)) - 2 * head * (n - 1))) < 1
    assert work.routed_flops(m, 10) == 10 * 6 * 2048 * 512


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
              "attrs": {"linear_tokens": 9 * 4000,
                        "moe_assignments": 4000 * 10 * 12,
                        "moe_rows": 60000, "moe_active_experts": 768}},
             {"name": "decode.step", "span_id": 3, "parent_id": 1,
              "t0": 10.3, "t1": 10.34,
              "attrs": {"context_tokens": 300000, "linear_rows": 9 * 90,
                        "window_context_tokens": 300000,
                        "moe_assignments": 90 * 10 * 12, "moe_rows": 1350,
                        "moe_active_experts": 600}}]
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
    samples = {"requests": [req], "all_requests": [req], "slots": 128,
               "window_counts": {"steps": 40, "step_seconds": 1.6,
                                 "prefills": 1, "prefill_seconds": 0.3,
                                 "tokens": 41, "requests": 1},
               "window_s": 45.0, "window_host": (9.0, 54.0),
               "kv_itemsize": 2,
               "pages_used": {"global": [0.25, 0.35]},
               "kernel_split": {
                   "moe_grouped_ffn": {"step_s": 0.02, "prefill_s": 0.01,
                                       "other_s": 0.0, "calls": 12},
                   "paged_decode_attention": {
                       "step_s": 0.03, "prefill_s": 0.0, "other_s": 0.0,
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
    assert mod.MOVES == "serve_ttft_mean_ms"
    value = mod.read(_run(monkeypatch))
    assert value is not None and np.isfinite(value) and value >= 0, name
    if "roofline" in name or "mfu" in name:
        assert 0 < value < 100, (name, value)


def test_kernel_readers_do_the_arithmetic_they_say(monkeypatch):
    read = lambda n, **kw: harness.load_module(REAL.metric_file(n)).read(
        _run(monkeypatch, **kw))
    assert abs(read("gdn.recurrent_step_ms_per_step") - 3.0) < 1e-9
    step = REAL.work("gdn_recurrent_step")
    need = step.roofline_seconds(2 * 9 * 90, 16, 32, 128, 128, PEAKS)
    assert abs(read("gdn.recurrent_step_roofline")
               - 100 * need / 0.030) < 1e-9
    chunk = REAL.work("gdn_chunk_prefill")
    need = chunk.roofline_seconds(9 * 4000, 16, 32, 128, 128, 2, PEAKS)
    assert abs(read("gdn.chunk_prefill_roofline")
               - 100 * need / 0.030) < 1e-9
    paged = REAL.work("paged_decode_attention")
    nbytes = 2 * 3 * paged.bytes_per_layer_step([300000], 2, 16, 256, 2)
    assert abs(read("kernels.paged_decode_roofline.gdn") - 100 * nbytes
               / PEAKS["hbm_bytes_per_s"] / 0.030) < 1e-9
    assert abs(read("kernels.paged_decode_ms_per_step.gdn") - 3.0) < 1e-9
    assert abs(read("moe.expert_ffn_ms_per_step.gdn") - 2.0) < 1e-9
    assert abs(read("moe.local_assignment_share.gdn") - 100.0
               * (60000 + 2 * 1350) / (480000 + 2 * 10800)) < 1e-9
    assert read("moe.active_experts_per_layer_step.gdn") == 50.0
    assert abs(read("decode.kv_pool_used_share.gdn") - 30.0) < 1e-9
    assert abs(read("decode.state_rows_used_share")
               - 100.0 * (41 - 1) / 40 / 128) < 1e-9
    # ... and nothing where there is no trace, or no such spans
    for name in ("gdn.recurrent_step_ms_per_step",
                 "gdn.recurrent_step_roofline", "gdn.chunk_prefill_roofline",
                 "kernels.paged_decode_roofline.gdn"):
        assert read(name, trace=False) is None
    for name in ("gdn.recurrent_step_roofline", "gdn.chunk_prefill_roofline",
                 "kernels.paged_decode_roofline.gdn"):
        mod = harness.load_module(REAL.metric_file(name))
        run = _run(monkeypatch)
        monkeypatch.setattr(span_log, "records", lambda: [])
        assert mod.read(run) is None


def test_the_state_readers_are_silent_for_other_models(monkeypatch):
    """A program without the spans, or a model without linear layers (what
    the parent commit runs), gives these readers nothing to read."""
    other = harness.Cell(ROOT, "smallthinker_21b_a3b.decode_mixed_len")
    for name in ("decode.kv_pool_used_share.gdn",
                 "decode.state_rows_used_share",
                 "kernels.paged_decode_roofline.gdn"):
        run = _run(monkeypatch)
        run.config = other.config
        assert harness.load_module(REAL.metric_file(name)).read(run) is None
    run = _run(monkeypatch)
    run.work = other.work
    run.config = other.config
    mfu = harness.load_module(REAL.metric_file("decode.step_mfu.gdn"))
    assert mfu.read(run) is None


def test_every_control_of_the_rehearsal_is_refused_by_a_limit():
    """The traced rehearsal reads the comparison against each control —
    the reference in float8, with the rotation over the whole head, with
    the top-k weights left un-renormalised — from the TIMED programs'
    tokens and routing: each must be refused by the limits the traffic
    file says refuse it, and the sound reading not; the result line
    carries the readers that need no device trace."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", NAME, "--seed", "2147489120", "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = [ln for ln in proc.stdout.splitlines() if "] control " in ln]
    assert len(said) == len(CONTROLS)
    for name, ln in zip(CONTROLS, said):
        assert "control %s: refused by " % name in ln, ln
        # at the rehearsal size (float32, no rounding between program and
        # reference) every limit refuses every control
        for check in ("tokens_agree_with_reference",
                      "prefill_routing_agrees_with_reference",
                      "decode_routing_agrees_with_reference"):
            assert check in ln.split(";")[0], ln
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    assert {"moe.local_assignment_share.gdn", "decode.kv_pool_used_share.gdn",
            "decode.state_rows_used_share",
            "moe.active_experts_per_layer_step.gdn", "serve.tpot_p95_ms.gdn",
            "decode.step_ms_mean.gdn", "decode.slot_occupancy.gdn",
            "decode.host_ms_per_step.gdn"} <= got <= set(NEW_READERS)
    share = line["metrics"]["moe.local_assignment_share.gdn"]["value"]
    assert 35.0 < share < 65.0          # 8 of 16 held
    rows = line["metrics"]["decode.state_rows_used_share"]["value"]
    assert 0.0 <= rows <= 100.0
