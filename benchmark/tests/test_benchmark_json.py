"""BENCHMARK.json against the contract's limits that a test can hold, and
against the files it names."""
import fnmatch
import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert 2 <= len(bench["workloads"]) <= 24 and len(bench["configs"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    # a full check must fit: 2 + 14 x cells runs, with the full 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end" and not (
                        group == "per_layer" and key == "source"):
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    assert all(len(word) <= 200 for word in bench["command"])


def test_every_cells_files_resolve(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] and cell.get("platform", "tpu") == "tpu"
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg_file = os.path.join(ROOT, configs[w["config"]]["file"])
        with open(cfg_file) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        for sub, name in (("traffic", cell["traffic"] + ".json"),
                          ("drivers", cell["kind"] + ".py"),
                          ("reference", cfg["reference"])):
            assert os.path.exists(os.path.join(BENCH, sub, name)), (sub, name)
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           mix["generator"] + ".py"))
    assert {c["name"] for c in bench["configs"]} == {w["config"]
                                                    for w in bench["workloads"]}
    for path in [c["file"] for c in bench["configs"]] + bench["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_per_layer_entries_are_the_reader_files(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    readers = {}
    folder = os.path.join(BENCH, "layer_metrics")
    for fname in os.listdir(folder):
        if fname.endswith(".py"):
            spec = importlib.util.spec_from_file_location("m", os.path.join(folder, fname))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert fname == mod.NAME + ".py"
            readers[mod.NAME] = mod
    assert set(readers) == {m["name"] for m in bench["per_layer"]}
    layers = set()
    for m in bench["per_layer"]:
        mod = readers[m["name"]]
        assert (m["unit"], m["layer"], m["moves"]) == (mod.UNIT, mod.LAYER, mod.MOVES)
        layers.add(m["layer"])
        # reported only where the metric it moves is
        moved = e2e[m["moves"]].get("workloads", cells)
        for cell in m.get("workloads", cells):
            assert cell in moved, (m["name"], cell)
            assert any(fnmatch.fnmatch(cell, g) for g in mod.CELLS)
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_the_harness_names_no_cell(bench):
    """A cell, a configuration, a mix or a metric is a file and an entry:
    run.py, the drivers and the reduction know none of them by name."""
    words = [w["name"] for w in bench["workloads"]] \
        + [c["name"] for c in bench["configs"]] \
        + [w["traffic"] for w in bench["workloads"]]
    harness = [os.path.join(BENCH, "run.py"), os.path.join(BENCH, "spans.py")]
    for sub in ("drivers", "reduce"):
        harness += [os.path.join(BENCH, sub, f)
                    for f in os.listdir(os.path.join(BENCH, sub))
                    if f.endswith(".py")]
    for path in harness:
        with open(path) as f:
            text = f.read()
        for word in words:
            assert not re.search(rf"(?<![\w\-]){re.escape(word)}(?![\w\-])", text), \
                (path, word)
