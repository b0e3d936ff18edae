"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; they
are ``configs/<config>.json`` and ``traffic/<traffic>.json`` beside this
file.  The cell's analytic (from its traffic file) has its reference and
comparison in ``checks/<analytic>.py``.  Each per-layer metric is a reader
``metrics/<name>.py``.  Adding a cell or a metric adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path) -> ModuleType:
    """Import one file by path (its name may hold dots and hyphens)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload with everything it needs, resolved by name."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    check: ModuleType  # checks/<analytic>.py
    end_to_end: List[Dict]  # metric entries reported with --trace 0
    per_layer: List[Dict]  # metric entries reported with --trace 1
    readers: Dict[str, ModuleType]  # per-layer metric name -> reader


def benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Dict, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``bench``, with its files loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = load_json(here / "configs" / f"{w['config']}.json")
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    check = load_module(here / "checks" / f"{traffic['analytic']}.py")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py")
               for m in layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, check=check, end_to_end=e2e,
                per_layer=layer, readers=readers)


def peaks(device_kind: str, here: Path = HERE) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(here / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
