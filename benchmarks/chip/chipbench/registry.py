"""BENCHMARK.json, and the files each of its names resolves to.

A cell's configuration is the file its `configs` entry names; its traffic
is traffic/<mix>.json; a per-layer metric is read by metrics/<name>.py.
Adding a cell, a mix or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_file(root: str, bench: dict, cell: dict) -> str:
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {cell['config']!r} in BENCHMARK.json")


def traffic_file(here: str, cell: dict) -> str:
    return os.path.join(here, "traffic", cell["traffic"] + ".json")


def metrics_for(bench: dict, cell: dict, kind: str) -> list:
    """The cell's metrics of one kind ("end_to_end" or "per_layer"): those
    that list the cell, or list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader(here: str, name: str):
    """metrics/<name>.py's `read(run)`: the per-layer metric's value, or
    None where the run holds nothing to read."""
    path = os.path.join(here, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
