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
PLACEMENTS = ("device", "row_sharded", "parquet")
PARQUET_KEYS = ("compression", "dictionary", "data_page_version",
                "row_group_bytes", "data_page_bytes")


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
    check_placement(config, query)
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                query=query,
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))


def check_placement(config: Dict[str, Any], query: ModuleType) -> None:
    """The configuration's ``placement`` is known; a ``parquet`` block
    goes with the ``parquet`` placement and no other, holds exactly
    ``PARQUET_KEYS``, and the query names its tables' columns."""
    where = f"config {config.get('name')!r}"
    placement = config.get("placement")
    if placement not in PLACEMENTS:
        raise SpecError(f"{where}: unknown placement {placement!r}; "
                        f"placements: {PLACEMENTS}")
    block = config.get("parquet")
    if placement != "parquet":
        if block is not None:
            raise SpecError(f"{where}: a parquet block under the "
                            f"{placement!r} placement")
        return
    if not isinstance(block, dict):
        raise SpecError(f"{where}: the parquet placement needs a parquet "
                        f"block with {PARQUET_KEYS}")
    if set(block) != set(PARQUET_KEYS):
        raise SpecError(
            f"{where}: parquet block keys {sorted(block)}; missing "
            f"{sorted(set(PARQUET_KEYS) - set(block))}, unknown "
            f"{sorted(set(block) - set(PARQUET_KEYS))}")
    if not (isinstance(block["compression"], str)
            and isinstance(block["dictionary"], bool)
            and block["data_page_version"] in ("1.0", "2.0")
            and all(type(block[k]) is int and block[k] > 0
                    for k in ("row_group_bytes", "data_page_bytes"))):
        raise SpecError(f"{where}: parquet block {block}: compression is a "
                        f"codec name, dictionary a bool, data_page_version "
                        f"'1.0' or '2.0', the byte sizes positive integers")
    tables = getattr(query, "TABLES", None)
    if not (isinstance(tables, tuple) and tables and all(
            isinstance(t, tuple) and len(t) == 2 and isinstance(t[0], str)
            and isinstance(t[1], tuple) and t[1] for t in tables)):
        raise SpecError(f"{where}: query {query.__name__} does not name its "
                        f"tables' columns as TABLES = ((table, column_names),"
                        f" ...)")


def peaks(kind: str) -> Dict[str, Any]:
    """The published peaks of one device kind; a kind not in the table
    is an error, never a default."""
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in perfbench/peaks.json"
                        f" ({sorted(table)})")
    return table[kind]
