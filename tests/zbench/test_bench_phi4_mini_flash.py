"""The ``phi4_mini_flash_reasoning`` configuration's benchmark files: the
file holds the published config uncut, the reference's tree is the
program's and its parameters are the count the file states, the work
functions count what they say, every reader this configuration brought
returns a number where its source is there (and nothing where it is not),
and the traced rehearsal's controls are each refused by the limits the
traffic file names. CPU only."""
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

NAME = "phi4_mini_flash_reasoning.decode_reasoning"
CELL = harness.Cell(ROOT, NAME, rehearse=True)
REAL = harness.Cell(ROOT, NAME)
DRIVER = harness.load_module(CELL.driver_file)
PEAKS = harness.load_json(os.path.join(
    ROOT, "bench", "peaks.json"))["devices"]["TPU v5 lite"]
NEW_READERS = [m["name"] for m in REAL.benchmark["per_layer"]
               if m.get("workloads") == [NAME]]
CONTROLS = ["float8_weights", "lambda_zero", "window_off_in_layers_1_to_15"]
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


def test_the_cell_and_its_readers_are_declared():
    assert REAL.driver_name == "decode_open_loop_v3"
    assert (REAL.chips, REAL.config_name, REAL.traffic_name) == (
        1, "phi4_mini_flash_reasoning", "reasoning_poisson_p80")
    e2e = dict((m["name"], m) for m in REAL.benchmark["end_to_end"])
    assert NAME in e2e["serve_ttft_mean_ms"]["workloads"]
    assert {"mamba.recurrent_step_ms_per_step",
            "mamba.recurrent_step_roofline", "mamba.prefill_scan_roofline",
            "kernels.diff_paged_decode_ms_per_step",
            "kernels.diff_paged_decode_roofline",
            "kernels.diff_flash_prefill_roofline",
            "decode.state_rows_used_share.sambay",
            "decode.kv_pool_used_share.sambay_full",
            "decode.kv_pool_used_share.sambay_window",
            "decode.step_mfu.sambay"} <= set(NEW_READERS)
    for name in NEW_READERS:
        assert os.path.exists(REAL.metric_file(name)), name
    entry = [c for c in REAL.benchmark["configs"]
             if c["name"] == "phi4_mini_flash_reasoning"][0]
    assert entry["reduced"] == [] and entry["source"] == REAL.config[
        "source"].split(" ")[0]
    assert len(REAL.entry["why"]) <= 200


def test_full_size_file_holds_the_published_config_uncut():
    body = REAL.config
    for key, value in CATALOG.items():
        assert body[key] == value == body["published"][key], key
    assert body["reduced"] == []
    m = body["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["n_layers"],
            m["d_ff"], m["vocab_size"], m["sliding_window"], m["norm_eps"],
            m["tie_embeddings"], m["gate_act"]) == (
        2560, 40, 20, 32, 10240, 200064, 512, 1e-05, True, "silu")
    assert m["head_dim"] * m["n_heads"] == m["d_model"]
    assert (m["mamba_d_state"], m["mamba_d_conv"], m["mamba_expand"],
            m["mamba_dt_rank"]) == (16, 4, 2, 160)     # phi4flash defaults
    assert m["mamba_dt_rank"] == -(-m["d_model"] // 16)
    # mb_per_layer 2: Mamba on even layers; yoco_kv = layer 17, yoco_cross
    # from 18; the window below n / 2
    assert m["mamba_layout"] == [int(li % 2 == 0) for li in range(32)]
    assert m["window_layout"] == [int(li < 16) for li in range(32)]
    assert m["cross_from"] == 32 // 2 + 2 and m["pos_type"] == "none"
    assert m["diff_attn"] and m["attn_bias"] and m["ffn_gated"]
    kinds = REAL.reference().kinds(m)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    leaves = []
    for v in REAL.reference().param_tree(m).values():
        leaves += list(v.values()) if isinstance(v, dict) else [v]
    n = sum(int(np.prod(shape)) for shape, _kind in leaves)
    ffn, lns = 78643200, 10240
    layers = 9 * (41241600 + ffn + lns) + 9 * (19668864 + ffn + lns) \
        + 7 * (26214400 + ffn + lns) + 7 * (13112704 + ffn + lns)
    assert layers == 3340393984
    assert n == layers + 200064 * 2560 + 2 * 2560 == 3852562944
    assert "3,852,562,944" in body["parameters_here"]
    eng = body["engine"]
    assert eng["slots"] == 64 and eng["max_context"] >= 10240
    # 9 layers of 16 x 5120 float32 state and 3 x 5120 bf16 taps a row
    assert 9 * (16 * 5120 * 4 + 3 * 5120 * 2) == 3225600
    assert 0.9e9 * 2 < (eng["num_pages"] - 1) * eng["page_size"] * 5120 \
        < 2.1e9
    assert eng["window_pages"] * eng["page_size"] * 8 * 5120 < 2.2e9


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
    mb = lambda name: np.asarray(ours["mamba_layers"][name])
    assert np.allclose(mb("mb_a_log")[0, 0, 0], np.log(np.arange(1, 5)))
    steps = np.log1p(np.exp(mb("mb_dt_b")))
    assert abs(steps.min() - 1e-3) < 1e-6 and abs(steps.max() - 0.1) < 1e-4
    assert (mb("mb_d") == 1).all()
    lam = np.asarray(ours["layers"]["lambda_q1"])
    assert 0.05 < float(np.std(lam)) < 0.15
    assert abs(float(np.std(np.asarray(ours["layers"]["wq"]))) - 0.125) \
        < 0.01                                      # d_model ** -0.5
    real = DRIVER.model_config(REAL.config)
    assert real.cross_from == 18 and real.mamba_layout == (1, 0) * 16


def test_a_program_without_the_fields_refuses_the_cell_at_once():
    """What the parent commit does with these files: its TransformerConfig
    lacks the fields, so the driver refuses before any weight is drawn."""
    import dataclasses
    from mxnet_tpu.parallel import transformer as T
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(T.TransformerConfig)
        if f.name != "mamba_layout"])
    real = T.TransformerConfig
    T.TransformerConfig = old
    try:
        with pytest.raises(harness.Refused, match="mamba_layout"):
            DRIVER.model_config(REAL.config)
    finally:
        T.TransformerConfig = real


def test_the_schedule_holds_requests_the_reference_can_be_given():
    tr = REAL.traffic
    assert tr["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                   "min": 128, "max": 4096}
    assert tr["output_tokens"] == {"median": 2048, "sigma": 0.5,
                                   "min": 512, "max": 6144}
    plan = DRIVER.schedule(tr, 45.0, 3000000019, 200064)
    p = np.array([len(x) for _d, x, _o in plan])
    o = np.array([x for _d, _p, x in plan])
    assert 128 <= p.min() and p.max() <= 4096
    assert 512 <= o.min() and o.max() <= 6144
    assert (p + o).max() <= REAL.config["engine"]["max_context"]
    cap, long_min = tr["reference_max_tokens"], tr["reference_long_prompt_min"]
    # the reference's logits rows (an answer's longest) fit its padded
    # sequence, and that is whole blocks of its queries
    assert cap >= tr["output_tokens"]["max"]
    assert cap % CELL.reference().Q_BLOCK == 0
    fits = (p + o) <= cap
    assert (fits & (p >= long_min)).sum() >= 3       # a long one to compare
    assert (fits & (p < long_min)).sum() >= tr["reference_requests"] + 2
    assert [c["name"] for c in tr["reference_controls"]] == CONTROLS == [
        c["name"] for c in CELL.traffic["reference_controls"]]
    assert abs(tr["rate_per_s"] - 0.8 * tr["knee_per_s"]) < 1e-9
    for key in ("logit_gap_largest", "logit_gap_mean",
                "which_limit_refuses_which_control"):
        assert key in tr["tolerance_readings"], key


def test_mamba_work_against_hand_counts():
    """A row a layer: a 16 x 5120 float32 state read and written and its
    vectors; a prompt token a layer: the same operations and its vectors
    alone."""
    step = REAL.work("mamba_recurrent_step")
    assert step.flops(1, 16, 5120) == 7 * 16 * 5120 == 573440
    assert step.nbytes(1, 16, 5120) == 2 * 16 * 5120 * 4 \
        + (3 * 5120 + 32) * 4 == 716928
    rows = 48 * 9
    both = (rows * 716928 / PEAKS["hbm_bytes_per_s"],
            rows * 573440 / PEAKS["bf16_flops_per_s"])
    assert step.roofline_seconds(rows, 16, 5120, PEAKS) == both[0] \
        == max(both)                                 # bound by HBM
    assert re.match(step.TRACE_NAME, "_mamba_step.3")
    assert not re.match(step.TRACE_NAME, "_mamba_scan.2")
    scan = REAL.work("mamba_chunk_scan")
    assert scan.flops(1, 16, 5120) == 573440
    assert scan.nbytes(1, 16, 5120, 2) == 5120 * 8 + 32 * 4 == 41088
    tokens = 1024 * 9
    assert scan.roofline_seconds(tokens, 16, 5120, 2, PEAKS) == \
        tokens * 41088 / PEAKS["hbm_bytes_per_s"]
    assert re.match(scan.TRACE_NAME, "_mamba_scan")


def test_diff_attention_work_against_hand_counts():
    paged = REAL.work("diff_paged_decode")
    page = paged.page_bytes(128, 20, 64, 2)
    assert page == 2 * 128 * 20 * 64 * 2 == 655360
    # 48 rows of 2800 tokens: 22 pages each; rings of 5 live pages
    got = paged.step_bytes(48 * 22, 8, 48 * 5, 8, 48, 128, 40, 20, 64, 2)
    assert got == (48 * 22 * 8 + 48 * 5 * 8) * 655360 \
        + 2 * 48 * 16 * 40 * 128 * 2
    assert 6.5e9 < got < 7.5e9
    assert re.match(paged.TRACE_NAME, "_diff_paged_decode.12")
    assert not re.match(paged.TRACE_NAME, "_paged_decode.12")
    pre = REAL.work("diff_flash_prefill")
    assert pre.pairs(10) == 55 and pre.pairs(10, 4) == 10 + 6 * 4
    assert pre.flops(1024, 1, 8, 512, 40, 64) == 12 * 64 * 20 * (
        1024 * 1025 // 2 + 8 * (512 * 513 // 2 + 512 * 512))
    assert re.match(pre.TRACE_NAME, "_diff_flash_prefill.1")


def test_model_flops_of_the_model():
    work, m = REAL.work(REAL.config["work"]), REAL.config["model"]
    ffn = 3 * 2560 * 10240
    mamba = (2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120
             + 3.5 * 16 * 5120 + 5120 * 2560)
    attn = 2560 * 5120 + 2560 * 2560
    gmu, cross = 2 * 2560 * 5120, 2 * 2560 * 2560
    head = 2560 * 200064
    maps = 9 * mamba + 9 * attn + 7 * gmu + 7 * cross + 32 * ffn
    pair = 20 * 6 * 64
    assert work.token_flops(m, 0) == 2 * (maps + 16 * pair + head)
    # past the window only the full layer and its 7 readers grow
    assert work.token_flops(m, 3000) - work.token_flops(m, 2999) \
        == 2 * 8 * pair
    assert work.token_flops(m, 100) - work.token_flops(m, 99) \
        == 2 * 16 * pair
    # a prompt: the self-decoder over every position, the cross-decoder
    # and the logits over its last
    own = 9 * mamba + 9 * attn + 18 * ffn
    n = 100
    assert abs(work.prefill_flops(m, n) - 2 * (
        n * own + 9 * pair * n * (n + 1) // 2
        + 7 * (gmu + ffn) + 7 * (cross + ffn) + 7 * pair * n + head)) < 1


class _Trace(object):
    """A reduced trace that saw 30 ms of each kernel in 12 calls."""
    busy_s, window_s, idle_share = 2.0, 3.0, 1.0 / 3

    def seconds_matching(self, pattern):
        return 0.030, 12, [pattern]


def _run(monkeypatch, trace=True):
    """What a reader sees after a traced run of the cell: the samples the
    driver returns, a span log of one prefill and two steps."""
    steps = [{"name": "decode.iteration", "span_id": 1, "parent_id": None,
              "t0": 10.0, "t1": 10.5, "attrs": {"live": 1}},
             {"name": "decode.prefill", "span_id": 2, "parent_id": 1,
              "t0": 10.0, "t1": 10.3,
              "attrs": {"mamba_tokens": 9 * 1000,
                        "self_decoder_only_tokens": 999,
                        "cross_decoder_tokens": 1}},
             {"name": "decode.step", "span_id": 3, "parent_id": 1,
              "t0": 10.3, "t1": 10.34,
              "attrs": {"context_tokens": 100000, "mamba_rows": 9 * 40,
                        "state_rows": 40, "shared_kv_readers": 8,
                        "full_pages_read": 800, "window_pages_read": 200,
                        "window_context_tokens": 20480}}]
    again = [dict(steps[0], span_id=4, t0=10.51, t1=10.56),
             dict(steps[2], span_id=5, parent_id=4, t0=10.51, t1=10.55)]
    monkeypatch.setattr(span_log, "records", lambda: steps + again)
    monkeypatch.setattr(span_log, "decode_window", lambda run: [
        (steps[0], steps[1:]), (again[0], again[1:])])
    req = types.SimpleNamespace(
        due=10.0, sent=10.0, enq=10.0, admit=10.0, first=10.3, done=12.3,
        tokens=41, prompt_len=1000, want_tokens=41, failed=False,
        error=None)
    samples = {"requests": [req], "all_requests": [req], "slots": 64,
               "window_counts": {"steps": 40, "step_seconds": 1.6,
                                 "prefills": 1, "prefill_seconds": 0.3,
                                 "tokens": 41, "requests": 1},
               "window_s": 45.0, "window_host": (9.0, 54.0),
               "kv_itemsize": 2,
               "pages_used": {"global": [0.25, 0.35],
                              "window": [0.1, 0.2]},
               "kernel_split": None,
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
    assert abs(read("mamba.recurrent_step_ms_per_step") - 3.0) < 1e-9
    assert abs(read("kernels.diff_paged_decode_ms_per_step") - 3.0) < 1e-9
    step = REAL.work("mamba_recurrent_step")
    need = step.roofline_seconds(2 * 9 * 40, 16, 5120, PEAKS)
    assert abs(read("mamba.recurrent_step_roofline")
               - 100 * need / 0.030) < 1e-9
    scan = REAL.work("mamba_chunk_scan")
    need = scan.roofline_seconds(9 * 1000, 16, 5120, 2, PEAKS)
    assert abs(read("mamba.prefill_scan_roofline")
               - 100 * need / 0.030) < 1e-9
    paged = REAL.work("diff_paged_decode")
    nbytes = 2 * paged.step_bytes(800, 8, 200, 8, 40, 128, 40, 20, 64, 2)
    assert abs(read("kernels.diff_paged_decode_roofline") - 100 * nbytes
               / PEAKS["hbm_bytes_per_s"] / 0.030) < 1e-9
    pre = REAL.work("diff_flash_prefill")
    need = pre.flops(1000, 1, 8, 512, 40, 64) / PEAKS["bf16_flops_per_s"]
    assert abs(read("kernels.diff_flash_prefill_roofline")
               - 100 * need / 0.030) < 1e-9
    assert abs(read("decode.state_rows_used_share.sambay")
               - 100.0 * 40 / 64) < 1e-9
    assert abs(read("decode.kv_pool_used_share.sambay_full") - 30.0) < 1e-9
    assert abs(read("decode.kv_pool_used_share.sambay_window") - 15.0) \
        < 1e-9
    for name in ("mamba.recurrent_step_ms_per_step",
                 "mamba.recurrent_step_roofline",
                 "mamba.prefill_scan_roofline",
                 "kernels.diff_paged_decode_roofline",
                 "kernels.diff_flash_prefill_roofline",
                 "decode.state_rows_used_share.sambay"):
        assert read(name, trace=False) is None


def test_the_new_readers_are_silent_on_a_program_without_the_spans(
        monkeypatch):
    """The parent commit's engine writes none of these attributes: the
    readers that need them give nothing to read."""
    for name in ("mamba.recurrent_step_roofline",
                 "mamba.prefill_scan_roofline",
                 "kernels.diff_paged_decode_roofline",
                 "kernels.diff_flash_prefill_roofline",
                 "decode.state_rows_used_share.sambay"):
        mod = harness.load_module(REAL.metric_file(name))
        run = _run(monkeypatch)
        monkeypatch.setattr(span_log, "records", lambda: [])
        assert mod.read(run) is None, name


def test_every_control_of_the_rehearsal_is_refused_by_a_limit():
    """The traced rehearsal reads the comparison against each control —
    the reference in float8, with lambda 0, without the window — from the
    TIMED programs' tokens: each must be refused, and the sound reading
    not; the result line carries the readers that need no device trace."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", NAME, "--seed", "2147489121", "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = [ln for ln in proc.stdout.splitlines() if "] control " in ln]
    assert len(said) == len(CONTROLS)
    for name, ln in zip(CONTROLS, said):
        assert "control %s: refused by tokens_agree_with_reference" % name \
            in ln, ln
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    assert {"decode.state_rows_used_share.sambay",
            "decode.kv_pool_used_share.sambay_full",
            "decode.kv_pool_used_share.sambay_window",
            "decode.step_ms_mean.sambay", "decode.slot_occupancy.sambay",
            "serve.tpot_p95_ms.sambay"} <= got <= set(NEW_READERS)
