"""BENCHMARK.json against the files under benchmark/: every name has its
file, the files say the same as the manifest, and run.py names no cell,
configuration or metric. Run by hand:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


MANIFEST = load("BENCHMARK.json")


def test_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in MANIFEST[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        ns = [e["name"] for e in MANIFEST[k]]
        assert len(ns) == len(set(ns))
    ms = [e["name"] for k in ("end_to_end", "per_layer") for e in MANIFEST[k]]
    assert len(ms) == len(set(ms))
    lines = [e["why"] for e in MANIFEST["configs"] + MANIFEST["workloads"]]
    lines += [c["source"] for c in MANIFEST["configs"]] + MANIFEST["command"]
    lines += [m["layer"] for m in MANIFEST["per_layer"]]
    for text in lines:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    units = [m["unit"] for k in ("end_to_end", "per_layer") for m in MANIFEST[k]]
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", u) for u in units), units
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 2)


def test_configs_have_files_and_cells():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        doc = load(c["file"])
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        assert doc["guarantees"] and doc["assumed"]
        importlib.import_module("benchmark.reference." + doc["reference"])


def test_workloads_have_files():
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = load(f"benchmark/workloads/{w['name']}.json")
        for k in ("name", "config", "traffic", "why"):
            assert cell[k] == w[k], (w["name"], k)
        load(f"benchmark/traffic/{w['traffic']}.json")
        entry = importlib.import_module("benchmark.entries." + cell["entry"])
        assert callable(entry.run)


def test_metrics_have_files_and_readers():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m.get("workloads", [])) <= cells
        spec = load(f"benchmark/layer_metrics/{m['name']}.json")
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(reader.read)
        if "roofline" in spec:
            importlib.import_module("benchmark.rooflines." + spec["roofline"])


def test_run_py_names_nothing():
    with open(os.path.join(ROOT, "benchmark/run.py")) as f:
        src = f.read()
    names = [e["name"] for k in ("configs", "workloads", "per_layer")
             for e in MANIFEST[k]]
    names += [m["name"] for m in MANIFEST["end_to_end"] if m["name"] != "setup_s"]
    names += [load(f"benchmark/workloads/{w['name']}.json")["entry"]
              for w in MANIFEST["workloads"]]
    assert [n for n in names if n in src] == []


def test_every_file_under_paths_has_a_contract_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel
