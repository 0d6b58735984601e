"""A later PR adds a configuration, a traffic mix and a per-layer metric
with new files and new BENCHMARK.json entries only: shown on a copy of the
benchmark in a temporary directory, to which one made-up of each is added
and no file that was there is edited. CPU only; no jax import."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    assert not os.path.exists(path), "an addition may not replace a file"
    with open(path, "w") as f:
        f.write(text)


def test_a_made_up_config_mix_and_metric_are_files_and_entries(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    before = json.dumps(bm, sort_keys=True)

    # -- the files a later PR brings ----------------------------------------
    write(os.path.join(root, "bench/configs/madeup_net.json"), json.dumps({
        "source": "a paper", "published": {"width": 8}, "reduced": [],
        "assumed": {}, "departures": [], "deployment": "none",
        "cli_flags": {"network": "mlp", "num-classes": 10,
                      "image-shape": "1,8,8"}, "work": "resnet"}))
    write(os.path.join(root, "bench/reference/madeup_net.py"),
          "def forward(params, images):\n    return images\n")
    write(os.path.join(root, "bench/traffic/fit_madeup.json"), json.dumps({
        "driver": "fit_cli", "tpus": "0", "batch_size": 4,
        "warmup_steps": 2, "flags": {"benchmark": 1}, "trace_seconds": 1,
        "reference_rows": 2,
        "reference_tolerance": {"inference_forward": 0.1,
                                "training_forward": 0.1}}))
    write(os.path.join(root, "bench/metrics/fit_loop.madeup_ms.py"),
          'LAYER = "fit loop"\nUNIT = "ms"\nMOVES = "train_samples_per_s"\n'
          'DRIVERS = ("fit_cli",)\n\n\ndef read(run):\n'
          '    return max(s["wall"] for s in run.samples["steps"]) * 1e3\n')

    # -- and its entries: appended, nothing that was there is touched -----
    bm["configs"].append({"name": "madeup_net", "source": "a paper",
                          "file": "bench/configs/madeup_net.json",
                          "reduced": [], "why": "made up"})
    bm["workloads"].append({"name": "madeup_net.fit", "config": "madeup_net",
                            "traffic": "fit_madeup", "chips": 1,
                            "why": "made up"})
    for m in bm["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            # a new cell joins an end-to-end metric's list of cells
            m["workloads"] = m["workloads"] + ["madeup_net.fit"]
    bm["per_layer"].append({
        "name": "fit_loop.madeup_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "fit loop",
        "moves": "train_samples_per_s", "workloads": ["madeup_net.fit"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    # -- the harness finds all of it by name ----------------------------------
    cell = harness.Cell(root, "madeup_net.fit")
    assert cell.config["cli_flags"]["network"] == "mlp"
    assert cell.traffic["batch_size"] == 4 and cell.driver_name == "fit_cli"
    assert os.path.exists(cell.driver_file)
    assert cell.reference().forward(None, 3) == 3
    assert [m["name"] for m in cell.metrics("per_layer")] == [
        "fit_loop.madeup_ms"]
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "train_samples_per_s", "setup_s"]
    result = {"end_to_end": {"train_samples_per_s": 1.0, "setup_s": 1.0},
              "samples": {"steps": [{"wall": 0.25, "phase": "window"},
                                    {"wall": 0.5, "phase": "window"}]}}
    run = harness.Run(cell, None, result, None, 0)
    assert harness.read_per_layer(cell, run) == {
        "fit_loop.madeup_ms": {"value": 500.0, "unit": "ms"}}
    # the cells that were there read exactly what they read before
    old = harness.Cell(root, "resnet50.fit_1chip")
    assert "fit_loop.madeup_ms" not in [
        m["name"] for m in old.metrics("per_layer")]
    assert json.dumps(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), sort_keys=True) == before
