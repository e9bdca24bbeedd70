"""Resolves a cell by its name in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric sits in a file of its own, found by name:

* the configuration: the ``file`` its ``configs`` entry names;
* the traffic mix: ``perfbench/traffic/<traffic>.json``;
* the cell's limits for ``correct``: ``perfbench/cells/<workload>.json``;
* a per-layer metric's reader: ``perfbench/metrics/<metric>.py``, whose
  ``read(run)`` returns the value, or None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import json
import os

import pb_models

#: the benchmark's directory inside a checkout
DIR = "perfbench"


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def resolve(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config=_json(os.path.join(root, cfg_entry["file"])),
        traffic=_json(os.path.join(root, DIR, "traffic",
                                   f"{w['traffic']}.json")),
        limits=_json(os.path.join(root, DIR, "cells",
                                  f"{workload}.json"))["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader(root: str, metric: str):
    """The ``read(run)`` function of a per-layer metric."""
    path = os.path.join(root, DIR, "metrics", f"{metric}.py")
    mod = pb_models.load_file(path, "pb_metric_" + metric.replace(".", "_")
                              .replace("-", "_"))
    return mod.read
