"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the files
are ``chipbench/configs/<config>.json`` and ``chipbench/traffic/<traffic>
.json``, the limits of the cell's check ``chipbench/limits/<cell>.json``,
and the reader of per-layer metric ``<name>`` is the module
``chipbench/metrics/<name>.py`` with a ``read(ctx)`` function. A later
cell, mix or metric is a new file and a new entry, never an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    if not os.path.exists(path):
        raise SystemExit(f"chipbench: no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    return Cell(name=name, chips=int(entry["chips"]),
                config=_load_json("configs", entry["config"]),
                traffic=_load_json("traffic", entry["traffic"]),
                limits=_load_json("limits", name),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"chipbench: no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
