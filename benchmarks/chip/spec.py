"""The cell a run measures, assembled by name from ``BENCHMARK.json`` and the
files beside this one: ``configs/<config>.json``, ``traffic/<mix>.json``,
``limits/<cell>.json`` and ``metrics/<metric>.py``.  Adding a cell, a mix, a
configuration or a metric adds files and entries; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import Dict, List, Optional

from traffic import Mix

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Mix
    limits: Optional[Dict]          # {check: {"limit": x, ...}}, or None
    end_to_end: List[Dict]          # manifest entries reported by this cell
    per_layer: List[Dict]


def _for_cell(entries: List[Dict], cell: str) -> List[Dict]:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, manifest: Optional[Dict] = None,
              root: pathlib.Path = ROOT) -> Cell:
    manifest = manifest or load_json(root / "BENCHMARK.json")
    found = [w for w in manifest["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in manifest["configs"] if c["name"] == w["config"]][0]
    config = load_json(root / conf["file"])
    mix = Mix.from_dict(load_json(HERE / "traffic" / f"{w['traffic']}.json"))
    limits_path = HERE / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else None
    return Cell(name, w["chips"], config, mix, limits,
                _for_cell(manifest["end_to_end"], name),
                _for_cell(manifest["per_layer"], name))


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``."""
    return importlib.import_module(f"metrics.{name}").read


def family(config: Dict):
    """``families/<family>.py``: reference forward and decode costs."""
    return importlib.import_module(f"families.{config['family']}")
