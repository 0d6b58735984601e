"""BENCHMARK.json keeps to the contract's letter, and everything a cell
names resolves by name. CPU only; no jax import."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness                                   # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


BM = benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BM["command"]) <= 32 and all(map(line, BM["command"]))
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    script = BM["command"][1]
    assert any(script.startswith(p + "/") for p in BM["paths"])
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    # a full check with the full 24 cells must fit into 43200 s
    cells = 24
    assert (2 + 14 * cells) * (BM["run_seconds"] + 60) + cells * 2 * 90 \
        + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs():
    assert 1 <= len(BM["configs"]) <= 24
    names = [c["name"] for c in BM["configs"]]
    files = [c["file"] for c in BM["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = set(w["config"] for w in BM["workloads"])
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BM["paths"])
        assert len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"])
        assert c["name"] in used, "a configuration no cell uses"
        body = harness.load_json(os.path.join(ROOT, c["file"]))
        # the file states its source, what was reduced or assumed and
        # each departure of the repo's code from the published equations
        for key in ("source", "published", "reduced", "assumed",
                    "departures", "deployment"):
            assert key in body, (c["name"], key)
        assert body["reduced"] == c["reduced"]


def test_workloads():
    assert 1 <= len(BM["workloads"]) <= 24
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BM["workloads"]) // 4)


def test_metrics():
    e2e = dict((m["name"], m) for m in BM["end_to_end"])
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert 1 <= len(BM["per_layer"]) <= 128
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert line(m["layer"]) and m["moves"] in e2e
        # the metric it moves is reported in every cell where it is
        mine = set(m.get("workloads", CELLS))
        assert mine <= set(e2e[m["moves"]].get("workloads", CELLS))
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= set(CELLS)
    for cell in CELLS:
        reported = [m["name"] for m in BM["end_to_end"]
                    if cell in m.get("workloads", CELLS)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in BM["per_layer"])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("rehearse", [False, True])
def test_cell_resolves_by_name(name, rehearse):
    cell = harness.Cell(ROOT, name, rehearse=rehearse)
    assert os.path.exists(cell.driver_file)
    assert os.path.exists(cell.reference_file)
    assert cell.traffic["driver"] == cell.driver_name
    for entry in cell.metrics("per_layer"):
        assert os.path.exists(cell.metric_file(entry["name"])), entry["name"]


@pytest.mark.parametrize("entry", BM["per_layer"], ids=lambda m: m["name"])
def test_metric_file_agrees_with_its_entry(entry):
    cell = harness.Cell(ROOT, CELLS[0])
    mod = harness.load_module(cell.metric_file(entry["name"]))
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert callable(mod.read)
    drivers = set(harness.Cell(ROOT, c).driver_name
                  for c in entry.get("workloads", CELLS))
    assert drivers <= set(mod.DRIVERS)


def test_every_file_under_paths_is_named_from_a_names_letters():
    for p in BM["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused):
        harness.Cell(ROOT, "no.such_cell")
