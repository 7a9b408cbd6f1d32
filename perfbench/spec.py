"""Find a cell's configuration, traffic, query and metrics by name.

``BENCHMARK.json`` names every piece; each lives in a file of its own
under ``perfbench/``, so a later cell is new files and new entries only.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


class SpecError(ValueError):
    """The cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable[[Dict[str, Any]], Optional[float]]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    query: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"metric {name!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"perfbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries: List[Dict[str, Any]], cell: str) -> List[Metric]:
    return [Metric(m["name"], m["unit"], _reader(m["name"]))
            for m in entries if cell in m.get("workloads", [cell])]


def load_cell(name: str) -> Cell:
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      f"{w['traffic']}.json"))
    if config["chips"] != w["chips"]:
        raise SpecError(f"{name}: config {w['config']} is for "
                        f"{config['chips']} chips, the cell asks {w['chips']}")
    query = importlib.import_module(
        f"perfbench.configs.{config['schema']}.{traffic['query']}")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                query=query,
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))


def peaks(kind: str) -> Dict[str, Any]:
    """The published peaks of one device kind; a kind not in the table
    is an error, never a default."""
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in perfbench/peaks.json"
                        f" ({sorted(table)})")
    return table[kind]
