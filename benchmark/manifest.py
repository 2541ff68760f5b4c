"""``BENCHMARK.json`` and the data files it names.

Nothing here knows a cell, a configuration, a mix or a metric by name: each
is found by the name the manifest gives it,

- ``benchmark/configs/<config>.json`` (sizes; ``kind`` names
  ``benchmark/model_kinds/<kind>.py``, ``reference`` names
  ``benchmark/references/<reference>.py``),
- ``benchmark/traffic/<traffic>.json`` (parameters; ``kind`` names
  ``benchmark/traffic_kinds/<kind>.py``),
- ``benchmark/layer_metrics/<metric>.json`` (``reader`` names
  ``benchmark/layer_metrics/<reader>.py``, ``function`` the callable in it).
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts), "r", encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}: "
                   f"{[e['name'] for e in entries]}")


def load_config(manifest: Dict[str, Any], name: str,
                root: str = ROOT) -> Dict[str, Any]:
    entry = find(manifest["configs"], name, "configuration")
    cfg = load_json(root, entry["file"])
    cfg["name"] = name
    return cfg


def load_traffic(name: str) -> Dict[str, Any]:
    mix = load_json(HERE, "traffic", f"{name}.json")
    mix["name"] = name
    return mix


def load_layer_metric(name: str) -> Dict[str, Any]:
    spec = load_json(HERE, "layer_metrics", f"{name}.json")
    spec["name"] = name
    return spec


def module(package: str, name: str):
    """``benchmark/<package>/<name>.py``, imported by name."""
    return importlib.import_module(f"benchmark.{package}.{name}")


def cell_metrics(manifest: Dict[str, Any], group: str,
                 workload: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end``/``per_layer``) that the cell
    ``workload`` reports: those with no ``workloads`` key, or that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]
