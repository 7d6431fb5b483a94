"""Every data file loads, names and units keep to the contract's
alphabet, and BENCHMARK.json says what the files say."""

import importlib
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def docs(kind, root=BENCH):
    out = {}
    for fname in sorted(os.listdir(os.path.join(root, kind))):
        assert fname.endswith(".json"), fname
        with open(os.path.join(root, kind, fname)) as f:
            out[fname[:-5]] = json.load(f)
    return out


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units():
    for kind in ("configs", "workloads", "traffic", "layer_metrics", "end_to_end"):
        for name in docs(kind):
            assert NAME.match(name), (kind, name)
    for kind in ("layer_metrics", "end_to_end"):
        for name, doc in docs(kind).items():
            assert UNIT.match(doc["unit"]), (name, doc["unit"])
            assert doc["better"] in ("lower", "higher")
            assert doc["source"] in SOURCES
            assert callable(importlib.import_module(
                f"benchmark.readers.{doc['reader']}").read)
    for name, doc in docs("end_to_end").items():
        assert doc["source"] in ("host_clock", "device_trace"), name


@pytest.mark.parametrize("root", [BENCH, os.path.join(BENCH, "tests", "rehearsal")])
def test_cells_name_files_that_exist_and_metrics_they_report(root):
    configs, mixes = docs("configs", root), docs("traffic", root)
    ends, layers = docs("end_to_end"), docs("layer_metrics")
    for name, cell in docs("workloads", root).items():
        assert cell["config"] in configs and cell["traffic"] in mixes, name
        assert cell["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        assert set(cell["end_to_end"]) <= set(ends)
        assert cell["per_layer"] and set(cell["per_layer"]) <= set(layers)
        for metric in cell["per_layer"]:
            assert layers[metric]["moves"] in cell["end_to_end"], (name, metric)
        assert 0 < len(cell["why"]) and "\n" not in cell["why"]
    for name, conf in configs.items():
        assert conf["source"] and isinstance(conf["reduced"], list), name
        for key in conf["reduced"]:
            assert key in conf and NAME.match(key)
            assert not re.search(r"(_dim|_rank|_size)$", key), key


def test_manifest_agrees_with_the_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"] and m["command"][-1] == "benchmark/run.py"
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    configs, cells = docs("configs"), docs("workloads")
    ends, layers = docs("end_to_end"), docs("layer_metrics")
    for c in m["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert c["source"] == configs[c["name"]]["source"]
        assert c["reduced"] == configs[c["name"]]["reduced"]
        assert len(c["why"]) <= 200
    assert {c["name"] for c in m["configs"]} == {w["config"] for w in m["workloads"]}
    for w in m["workloads"]:
        cell = cells[w["name"]]
        assert (w["config"], w["traffic"], w["chips"]) == (
            cell["config"], cell["traffic"], cell["chips"])
        assert 0 < len(w["why"]) <= 200
    listed = {w["name"] for w in m["workloads"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(listed) // 4)
    for kind, defs in (("end_to_end", ends), ("per_layer", layers)):
        key = "end_to_end" if kind == "end_to_end" else "per_layer"
        for entry in m[kind]:
            doc = defs[entry["name"]]
            for field in ("unit", "better", "source"):
                assert entry[field] == doc[field], (entry["name"], field)
            reports = {n for n in listed if entry["name"] in cells[n][key]}
            assert reports, entry["name"]
            assert set(entry.get("workloads", listed)) == reports, entry["name"]
            if kind == "end_to_end":
                assert 0.01 <= entry["bound"] <= 0.1
            else:
                assert entry["layer"] == doc["layer"] and entry["moves"] == doc["moves"]
    used = {n for c in listed for n in cells[c]["end_to_end"] + cells[c]["per_layer"]}
    assert used == {e["name"] for e in m["end_to_end"] + m["per_layer"]}
