"""Finding a cell's files by the names in the manifest.

The manifest (``BENCHMARK.json`` at the root of the checkout) is the only
index. A cell is an entry of its ``workloads``: a configuration, a traffic
mix and the chips it needs. Everything that belongs to one configuration,
one traffic mix, one data generator, one driver, one reference or one
per-layer metric is a file of its own in a directory named after its kind
under one of the manifest's ``paths`` (searched in order):

    configs/<name>.json     model and its arguments, data, training, check
    traffic/<name>.json     federation size, cohort, cadence, driver
    generators/<name>.py    build(data, clients, seed) -> the federation
    drivers/<name>.py       build(...) -> api, evaluate(api, round) -> dict
    references/<name>.py    the plain reference the check compares with
    metrics/<name>.json     reader + arguments of one per-layer metric
    readers/<name>.py       read(ctx, **args) -> float | None

so a later change adds files and manifest entries and edits none. Nothing
here, or anywhere under ``paths``, tests a cell's or a configuration's
name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the manifest with its files read."""

    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    paths: List[str]

    @property
    def clients(self) -> int:
        """The federation's size: the traffic mix's, or with ``"reference"``
        the configuration's own."""
        asked = self.traffic["clients"]
        return int(self.config["data"]["clients"] if asked == "reference"
                   else asked)

    def find(self, kind: str, name: str, ext: str) -> str:
        return find_file(self.paths, kind, name, ext)

    def module(self, kind: str, name: str):
        return load_module(self.find(kind, name, ".py"))


def find_file(paths: List[str], kind: str, name: str, ext: str) -> str:
    """``<path>/<kind>/<name><ext>`` in the first of ``paths`` that has
    it."""
    tried = []
    for base in paths:
        candidate = os.path.join(ROOT, base, kind, name + ext)
        if os.path.isfile(candidate):
            return candidate
        tried.append(candidate)
    raise FileNotFoundError(
        f"no {kind} file named {name!r}; looked for {tried}")


def load_module(path: str):
    """A Python file under ``paths`` as a module, by its path."""
    rel = os.path.relpath(path, ROOT)
    name = "_bench_" + rel[:-3].replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, manifest_path: str = MANIFEST) -> Cell:
    manifest = load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest_path}; it has "
                       f"{sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(ROOT, configs[entry["config"]]["file"]))
    paths = list(manifest["paths"])
    traffic = load_json(find_file(paths, "traffic", entry["traffic"],
                                  ".json"))
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"], config=config,
        traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
        paths=paths)
