"""The benchmark's data files, found by name.

A cell is ``workloads/<cell>.json``; it names its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``)
and its role (``roles/<role>.py``).  A metric is ``metrics/<metric>.json``
and names its reader (``readers/<reader>.py``) and the roles or cells it
applies to (and, where only some families have what it reads, the
builder's function that says which).  A later PR adds files; nothing here
lists them.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: where workloads/, configs/ and traffic/ are looked for, first hit wins;
#: ``run.py --data-dir`` puts the rehearsal's toy files in front
DATA_DIRS = [HERE]


def _read(kind: str, name: str) -> Dict[str, Any]:
    for base in DATA_DIRS:
        path = os.path.join(base, kind, name + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)
    known = sorted({os.path.basename(p)[:-5] for base in DATA_DIRS for p in
                    glob.glob(os.path.join(base, kind, "*.json"))})
    raise SystemExit(f"benchmark: no {kind}/{name}.json "
                     f"(there are: {', '.join(known)})")


def all_cells() -> List[str]:
    return sorted(os.path.basename(p)[:-5] for p in
                  glob.glob(os.path.join(HERE, "workloads", "*.json")))


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's file, with its traffic mix loaded under ``mix``."""
    cell = _read("workloads", name)
    if cell.get("name") != name:
        raise SystemExit(f"benchmark: workloads/{name}.json names itself "
                         f"{cell.get('name')!r}")
    cell["mix"] = _read("traffic", cell["traffic"])
    return cell


def load_config(name: str) -> Dict[str, Any]:
    return _read("configs", name)


def apply_overrides(cell: Dict[str, Any], overrides: List[str]) -> None:
    """``--set mix.rate=3.5``: a value of the cell for a sweep or a sizing
    question.  The driver never passes one; a run that has any says so in
    its result line."""
    for item in overrides or []:
        key, _, raw = item.partition("=")
        try:
            value = json.loads(raw)
        except ValueError:
            value = raw
        node = cell
        parts = key.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value


def load_metrics(cell: Dict[str, Any], end_to_end: bool
                 ) -> List[Dict[str, Any]]:
    """Every metric file that applies to this cell, by its role or by its
    name, of the kind asked for."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if bool(m.get("end_to_end")) != end_to_end:
            continue
        if metric_applies(m, cell):
            out.append(m)
    return out


def metric_applies(metric: Dict[str, Any], cell: Dict[str, Any]) -> bool:
    """By the cell's role or name; and, for a metric of something only
    some families have (a kernel), where the configuration's builder says
    this cell has it: ``where_builder_gives`` names a function of the
    builder, ``(config, mix) -> shapes or None``.  A cell is listed under
    a metric only where its reader will find something to read."""
    roles = metric.get("roles", [])
    if not (roles == "*" or cell["role"] in roles
            or cell["name"] in metric.get("workloads", [])):
        return False
    ask = metric.get("where_builder_gives")
    if ask is None:
        return True
    config = load_config(cell["config"])
    return getattr(builder(config["builder"]), ask)(
        config, cell["mix"]) is not None


def module(kind: str, name: str):
    """``roles``, ``builders``, ``references`` or ``readers``, by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def role(name: str):
    return module("roles", name)


def builder(name: str):
    return module("builders", name)


def reference(name: str):
    return module("references", name)


def reader(name: str):
    return module("readers", name).read
