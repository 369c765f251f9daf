"""BENCHMARK.json against the contract's structural rules, and the rule
that the harness is driven by data."""

import os
import re

import pytest

from benchmark.harness import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return spec.load_json(spec.MANIFEST)


def test_keys_counts_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in manifest[kind]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(len(e["why"]) <= 200 for kind in ("configs", "workloads")
               for e in manifest[kind])
    assert os.path.getsize(spec.MANIFEST) <= 64 * 1024


def test_cells(manifest):
    cells = manifest["workloads"]
    assert [c["name"] for c in cells] == [
        "fedcifar100_resnet18gn.dense", "fedcifar100_resnet18gn.mesh4",
        "femnist_cnn.powerlaw", "femnist_cnn.resident"]
    assert sum(c["chips"] == 4 for c in cells) == 1
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    configs = {c["name"]: c for c in manifest["configs"]}
    assert {c["config"] for c in cells} == set(configs)
    for c in configs.values():
        assert c["file"].startswith("benchmark/configs/")
        assert c["reduced"] == spec.load_json(
            os.path.join(ROOT, c["file"]))["reduced"] == []


def test_metrics(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert set(end) == {"rounds_per_s", "eval_s", "setup_s"}
    assert "workloads" not in end["rounds_per_s"]
    assert "workloads" not in end["setup_s"]
    assert end["setup_s"]["bound"] == 0.1
    for m in end.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {c["name"] for c in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in end
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    # every cell reports at least one per-layer metric
    for name in cells:
        assert spec.load_cell(name).per_layer


def test_every_named_file_exists_and_every_reader_loads(manifest):
    for entry in manifest["workloads"]:
        cell = spec.load_cell(entry["name"])
        cell.module("drivers", cell.traffic["driver"])
        cell.module("references", cell.config["reference"])
        for metric in cell.per_layer:
            described = spec.load_json(cell.find("metrics", metric["name"],
                                                 ".json"))
            assert callable(cell.module("readers", described["reader"]).read)


def test_no_code_under_paths_names_a_cell_or_a_configuration(manifest):
    """Adding a cell must never need an edit: no Python file of the
    benchmark (its tests and tools apart) may know a cell, configuration or
    traffic mix by name."""
    words = {w for e in manifest["workloads"]
             for w in (e["name"], e["config"], e["traffic"])}
    for folder, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if any(part in folder for part in ("tests", "tools", "_out",
                                           "__pycache__")):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(folder, f)).read()
                found = [w for w in words if re.search(
                    rf"[\"']{re.escape(w)}[\"']", text)]
                assert not found, (f, found)
