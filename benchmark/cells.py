"""Find a cell, its configuration and its traffic mix by name.

A cell is `<root>/workloads/<name>.json`; it names a configuration
(`<root>/configs/<config>.json`) and a traffic mix
(`<root>/traffic/<traffic>.json`). `<root>` is `benchmark/` for the cells of
`BENCHMARK.json` and `benchmark/tests/cells/` for the tiny CPU cells of the
benchmark's own tests, which are added exactly as a later PR adds a cell:
as files, with no edit to a file that is there.
"""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)


class CellError(ValueError):
    """A cell, or something it names, cannot be found or is malformed."""


def _load(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        have = sorted(n[:-5] for n in os.listdir(os.path.join(root, kind))
                      if n.endswith(".json")) \
            if os.path.isdir(os.path.join(root, kind)) else []
        raise CellError(f"no {kind[:-1]} {name!r} under {root} "
                        f"(there: {have})") from None


def load_cell(name: str, root: str = BENCH_DIR) -> dict:
    """The cell with its configuration and traffic files read in."""
    cell = dict(_load(root, "workloads", name))
    for key in ("config", "traffic", "job", "chips", "end_to_end",
                "layer_metrics"):
        if key not in cell:
            raise CellError(f"cell {name!r} lacks {key!r}")
    cell["name"] = name
    cell["config_data"] = _load(root, "configs", cell["config"])
    cell["traffic_data"] = _load(root, "traffic", cell["traffic"])
    cell.setdefault("platform", "tpu")
    return cell


def _module(package: str, name: str):
    """`benchmark.<package>.<name with - and . as _>`; one file per job kind,
    family or per-layer metric, found by name."""
    mod = name.replace("-", "_").replace(".", "_")
    try:
        return importlib.import_module(f"benchmark.{package}.{mod}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{package}.{mod}":
            raise
        raise CellError(f"no module benchmark/{package}/{mod}.py for "
                        f"{name!r}") from None


def job_module(cell: dict):
    return _module("jobs", cell["job"])


def family_module(config: dict):
    return _module("families", config["family"])


def reference_module(config: dict):
    return _module("reference", config["family"])


def metric_module(name: str):
    return _module("layer_metrics", name)
