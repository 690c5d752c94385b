"""Find a cell's files by its name.

``workloads/<cell>.json`` names the cell's configuration and traffic mix;
``configs/<config>.json`` and ``traffic/<traffic>.json`` hold them.  A
later PR adds a cell by adding files; nothing here lists cells.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    per_layer: List[str]


def _read(kind: str, name: str) -> Dict[str, Any]:
    if not _NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain name")
    path = os.path.join(ROOT, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    w = _read("workloads", name)
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=int(w["chips"]), config=_read("configs", w["config"]),
                traffic=_read("traffic", w["traffic"]),
                per_layer=list(w["per_layer"]))


def override(cell: Cell, sizes: Dict[str, Dict[str, Any]]) -> Cell:
    """The cell with ``sizes["config"]`` / ``sizes["traffic"]`` laid over
    its files' values: the CPU rehearsal's tiny sizes (``run.py
    --rehearse``), never a measured run."""
    return dataclasses.replace(
        cell, config={**cell.config, **sizes.get("config", {})},
        traffic={**cell.traffic, **sizes.get("traffic", {})},
        chips=int(sizes.get("chips", cell.chips)))
