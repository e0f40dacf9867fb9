"""Find a cell's pieces by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; this
module loads them from ``configs/<name>.json`` and ``traffic/<name>.json``,
and the code that belongs to them from ``kinds/<kind>.py``,
``reference/<family>.py`` and ``metrics/<metric>.py``. Adding a cell, a
configuration, a mix or a per-layer metric is adding files and manifest
entries: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (file names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str, root: pathlib.Path = BENCH) -> dict:
    """A mix's parameters."""
    return load_json(root / "traffic" / f"{name}.json")


@dataclass
class Cell:
    """Everything one run of one cell needs, found by name."""
    name: str
    entry: dict                       # the manifest's workload entry
    config: dict                      # configs/<config>.json
    traffic: dict                     # traffic/<mix>.json
    end_to_end: List[dict]            # metrics this cell reports, trace 0
    per_layer: List[dict]             # metrics this cell reports, trace 1
    root: pathlib.Path

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def runner(self) -> ModuleType:
        """The module that runs a cell of this kind (``run(run)``)."""
        return load_module(self.root / "kinds" / f"{self.kind}.py")

    def reference(self) -> ModuleType:
        return load_module(self.root / "reference"
                           / f"{self.config['family']}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "metrics" / f"{metric}.py")


def _reported_in(metric: dict, cell: str, e2e_of: Dict[str, List[str]]
                 ) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:                 # an end-to-end metric on every cell
        return True
    return cell in e2e_of.get(moves, [])


def load_cell(name: str, manifest_path: Optional[pathlib.Path] = None,
              root: pathlib.Path = BENCH) -> Cell:
    manifest = load_json(manifest_path or CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in the manifest; it has "
                       f"{sorted(cells)}")
    entry = cells[name]
    e2e_of = {m["name"]: m.get("workloads", list(cells))
              for m in manifest["end_to_end"]}
    return Cell(
        name=name, entry=entry,
        config=load_json(root / "configs" / f"{entry['config']}.json"),
        traffic=load_traffic(entry["traffic"], root),
        end_to_end=[m for m in manifest["end_to_end"]
                    if _reported_in(m, name, e2e_of)],
        per_layer=[m for m in manifest["per_layer"]
                   if _reported_in(m, name, e2e_of)],
        root=root)


def load_peaks(device_kind: str, root: pathlib.Path = BENCH) -> dict:
    """The device's published peaks; a device not in the table is an error."""
    table = load_json(root / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(table['devices'])})")
    return table["devices"][device_kind]
