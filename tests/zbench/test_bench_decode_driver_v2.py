"""The second serving driver's own arithmetic: the tree its reference
declares is the one the program's ``init_transformer_params`` builds for
the configuration's ``model``, the schedule draws weighted classes of
prompts from ``schedule_seed`` alone, and the work functions add up. CPU
only."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402

NAME = "smallthinker_21b_a3b.decode_mixed_len"
CELL = harness.Cell(ROOT, NAME, rehearse=True)
REAL = harness.Cell(ROOT, NAME)
DRIVER = harness.load_module(CELL.driver_file)


def test_reference_tree_is_the_programs_layout():
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.transformer import init_transformer_params
    cfg = DRIVER.model_config(CELL.config)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    theirs, _specs = init_transformer_params(cfg, mesh, seed=0)
    make = lambda seed: DRIVER.make_params(
        CELL.reference(), CELL.config, seed, jax.devices()[0])
    ours = make(3000000019)
    sig = lambda t: jax.tree_util.tree_map(
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    assert sig(ours) == sig(theirs)
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    assert "head" in ours and "pos" not in ours and "lnf_b" not in ours
    # normal(0, 0.02) maps drawn a layer at a time, unit gains; the
    # query and key maps at width ** -0.5, so that scores are of order one
    gate = np.asarray(ours["layers"]["we_gate"])
    assert abs(float(np.std(gate)) - 0.02) < 2e-3
    for name in ("wq", "wk"):
        assert abs(float(np.std(np.asarray(ours["layers"][name])))
                   - cfg.d_model ** -0.5) < 5e-3
    assert abs(REAL.reference().init_std(
        "unit_scores", REAL.config["model"]) - 0.02) < 3e-4
    assert not np.array_equal(gate[0, 0], gate[0, 1])
    assert float(np.min(np.asarray(ours["lnf_g"]))) == 1.0
    assert np.array_equal(np.asarray(ours["embed"]),
                          np.asarray(make(3000000019)["embed"]))
    assert not np.array_equal(np.asarray(ours["embed"]),
                              np.asarray(make(7)["embed"]))


def test_full_size_file_holds_the_published_config_cut_in_depth_only():
    body, pub = REAL.config, REAL.config["published"]
    changed = [k for k in pub if k != "parameters" and body[k] != pub[k]]
    assert changed == body["reduced"] == ["num_hidden_layers"]
    assert (pub["num_hidden_layers"], body["num_hidden_layers"]) == (52, 12)
    m = body["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"], m["num_experts"], m["moe_top_k"], m["vocab_size"],
            m["max_len"], m["sliding_window"], m["rope_base"],
            m["norm_eps"], m["tie_embeddings"], m["n_layers"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_ffn_hidden_size"], pub["moe_num_primary_experts"],
        pub["moe_num_active_primary_experts"], pub["vocab_size"],
        pub["max_position_embeddings"], pub["sliding_window_size"],
        pub["rope_theta"], pub["rms_norm_eps"], pub["tie_word_embeddings"],
        body["num_hidden_layers"])
    # three whole periods of the published layouts
    assert m["window_layout"] == pub["sliding_window_layout"][:12] \
        == [0, 1, 1, 1] * 3
    assert m["rope_layout"] == pub["rope_layout"][:12]
    leaves = []
    for v in REAL.reference().param_tree(m).values():
        leaves += list(v.values()) if isinstance(v, dict) else [v]
    n = sum(int(np.prod(shape)) for shape, _kind in leaves)
    assert n == 12 * 398627840 + 2 * 151936 * 2560 + 2560 == 5561448960
    eng = body["engine"]
    assert eng["max_context"] == pub["max_position_embeddings"]
    assert eng["max_context"] // eng["page_size"] == 1024


def test_every_seed_gets_the_same_classes_gaps_and_lengths():
    tr = REAL.traffic
    classes = tr["prompt_tokens"]
    assert [c["name"] for c in classes] == ["short", "long"]
    assert [c["weight"] for c in classes] == [0.75, 0.25]
    # every long prompt is past the window; every short one inside it
    window = REAL.config["model"]["sliding_window"]
    assert classes[1]["min"] > window > classes[0]["max"]
    ramp, seconds = tr["ramp_seconds"], 45.0
    a = DRIVER.schedule(tr, seconds, 3000000019, 151936)
    b = DRIVER.schedule(tr, seconds, 11, 151936)
    n_ramp = round(tr["rate_per_s"] * ramp)
    n_win = round(tr["rate_per_s"] * seconds)
    assert len(a) == len(b) == n_ramp + n_win
    shape = lambda plan: [(round(d, 9), len(p), o) for d, p, o in plan]
    assert shape(a)[:n_ramp] == shape(b)[:n_ramp]
    wa, wb = a[n_ramp:], b[n_ramp:]
    sizes = lambda plan: [(len(p), o) for _d, p, o in plan]
    assert sizes(wa) != sizes(wb) and sorted(sizes(wa)) == sorted(sizes(wb))
    assert any(sizes(wa) == sizes(wb)[k:] + sizes(wb)[:k]
               for k in range(n_win))
    assert sum(o for _d, _p, o in wa) == sum(o for _d, _p, o in wb)
    assert a == DRIVER.schedule(tr, seconds, 3000000019, 151936)
    # the clips of each class hold, and about a quarter is long
    p = np.array([len(x) for _d, x, _o in a])
    long_ = p >= classes[1]["min"]
    assert p[long_].max() <= classes[1]["max"]
    assert classes[0]["min"] <= p[~long_].min()
    assert p[~long_].max() <= classes[0]["max"]
    assert 0.1 < long_.mean() < 0.4
    o = np.array([x for _d, _p, x in a])
    assert tr["output_tokens"]["min"] <= o.min()
    assert o.max() <= tr["output_tokens"]["max"]
    assert a[0][1] != b[0][1]           # the tokens are the seed's
    # the first driver's single lognormal still draws as it did
    one = dict(tr, prompt_tokens=classes[0])
    assert all(len(x) <= classes[0]["max"]
               for _d, x, _o in DRIVER.schedule(one, 5.0, 1, 100))


def test_a_program_without_the_fields_is_refused():
    import pytest
    broken = dict(CELL.config, model=dict(CELL.config["model"],
                                          no_such_field=1))
    with pytest.raises(harness.Refused):
        DRIVER.model_config(broken)


def test_expert_kernel_work_by_regime():
    work = REAL.work("moe_grouped_ffn")
    peaks = harness.load_json(os.path.join(
        ROOT, "bench", "peaks.json"))["devices"]["TPU v5 lite"]
    h, f = 2560, 768
    # a 32-slot decode step of one layer: 192 rows over ~61 experts,
    # bound by the experts' bytes
    step = work.roofline_seconds(192, 61, h, f, 2, peaks)
    assert step == work.nbytes(192, 61, h, f, 2) / peaks["hbm_bytes_per_s"]
    assert work.nbytes(192, 61, h, f, 2) == 61 * 3 * h * f * 2 \
        + 2 * 192 * h * 2
    # a 16384-token prefill of one layer in four chunks: bound by the MXU
    rows = 16384 * 6
    assert work.flops(rows, h, f) == 6 * rows * h * f
    assert work.roofline_seconds(rows, 4 * 64, h, f, 2, peaks) \
        == work.flops(rows, h, f) / peaks["bf16_flops_per_s"]
    import re
    assert re.match(work.TRACE_NAME, "_moe_grouped_ffn.12")
    assert not re.match(work.TRACE_NAME, "_paged_decode.3")


def test_model_flops_follow_each_layers_rule():
    work = REAL.work(REAL.config["work"])
    m = REAL.config["model"]
    maps = 2 * (20971520 + 163840 + 6 * 5898240)      # a token, a layer
    head = 2 * 2560 * 151936
    # position 0 attends itself alone in every layer
    assert work.token_flops(m, 0, True) == 12 * maps + 12 * 4 * 3584 + head
    # deep in a sequence: 3 global layers see it all, 9 see the window
    pos = 10000
    assert work.token_flops(m, pos, False) == 12 * maps + 4 * 3584 * (
        3 * (pos + 1) + 9 * 4096)
    # a prefill is its tokens' sum, the logits made once
    n = 5000
    assert work.prefill_flops(m, n) == sum(
        work.token_flops(m, p, False) for p in range(n)) + head


class _Reference(object):
    """Stands in for the plain reference: logits that prefer token
    (position + 1) % 7 by ``margin`` and experts (position + layer) % 5."""

    def __init__(self, margin, shift=0):
        self.margin, self.shift = margin, shift

    def forward(self, params, seq, model, pad_to, logits_from, logits_rows,
                weights_as=None):
        n = len(seq)
        pos = np.arange(logits_from, logits_from + logits_rows)
        logits = np.zeros((logits_rows, 7), np.float32)
        logits[np.arange(logits_rows), (pos + 1) % 7] = self.margin
        experts = ((np.arange(n)[None, :, None] + np.arange(2)[:, None, None]
                    + self.shift * (np.arange(n)[None, :, None] >= 6))
                   % 5 + np.zeros((2, n, 1), int))
        return logits, experts


def test_readings_split_the_routing_by_the_program_that_chose_it():
    prompt, tokens = [3, 1, 4, 1, 5], [5, 6, 0, 1]     # the row's (position + 1) % 7
    served = (np.arange(8)[None, :, None] + np.arange(2)[:, None, None]) % 5
    pick = {"prompt": prompt, "tokens": tokens,
            # the prefill's five positions, then one a decode step
            "experts": [served[:, :5]] + [served[:, i:i + 1]
                                          for i in (5, 6, 7)]}
    sound = DRIVER._readings(_Reference(1.0), None, {}, [pick], 16, 8)
    assert sound == {"tokens": 4, "gap_max": 0.0, "gap_mean": 0.0,
                     "argmax_share": 1.0, "prompts": [5],
                     "prefill_pairs": 10, "prefill_agreement": 1.0,
                     "prefill_agreement_by_request": [1.0],
                     "prefill_agreement_worst": 1.0,
                     "decode_pairs": 6, "decode_agreement": 1.0,
                     "decode_agreement_by_request": [1.0],
                     "decode_agreement_worst": 1.0}
    # a reference that routes positions 6 and 7 elsewhere: the decode
    # steps' share alone falls, by two of three positions in both layers
    moved = DRIVER._readings(_Reference(1.0, shift=1), None, {}, [pick],
                             16, 8)
    assert moved["prefill_agreement"] == 1.0
    assert abs(moved["decode_agreement"] - 1 / 3.0) < 1e-9
    # ... and beside three requests it agrees with, the limit still sees
    # the one it does not: the worst request is judged, not the pool
    both = DRIVER._readings(_Reference(1.0, shift=1), None, {},
                            [dict(pick, tokens=tokens[:2],
                                  experts=pick["experts"][:2])] * 3
                            + [pick], 16, 8)
    assert both["decode_agreement_by_request"] == [1.0] * 3 + [0.3333]
    assert abs(both["decode_agreement"] - 2 / 3.0) < 1e-9
    assert abs(both["decode_agreement_worst"] - 1 / 3.0) < 1e-9
    # the engine's tokens are one off the reference's choice everywhere
    off = dict(pick, tokens=[(t + 1) % 7 for t in tokens])
    gaps = DRIVER._readings(_Reference(0.5), None, {}, [off], 16, 8)
    assert (gaps["gap_max"], gaps["gap_mean"], gaps["argmax_share"]) \
        == (0.5, 0.5, 0.0)
    limits = {"reference_logit_tolerance": 0.6,
              "reference_mean_logit_gap_max": 0.1,
              "reference_prefill_routing_agreement_min": 0.9,
              "reference_decode_routing_agreement_min": 0.9}
    verdict = lambda r: [ok for _n, ok, _d in DRIVER._judge(r, limits)]
    assert verdict(sound) == [True, True, True]
    assert verdict(moved) == [True, True, False]
    # the largest gap passes its tolerance; the MEAN gap refuses it
    assert verdict(gaps) == [False, True, True]


def test_every_control_of_the_rehearsal_is_refused_by_a_limit():
    """The traced rehearsal reads the comparison against each control —
    the reference in float8, with a rotation in every layer, without the
    window — from the TIMED programs' tokens and routing: each must be
    refused, and the sound reading not."""
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", NAME, "--seed", "2147489120", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = [ln for ln in proc.stdout.splitlines() if "] control " in ln]
    names = [c["name"] for c in CELL.traffic["reference_controls"]]
    assert names == ["float8_weights", "rotary_in_every_layer", "no_window"]
    assert len(said) == len(names)
    for name, ln in zip(names, said):
        assert "control %s: refused by " % name in ln, ln
    assert [c["name"] for c in REAL.traffic["reference_controls"]] == names
    assert '"correct": true' in proc.stdout
