"""Finds a configuration's model and adapter files by the ``model`` key of
its configuration file, and the inputs mixin every adapter's task uses.

``models/<model>.py`` is the benchmark's own: weights, client data,
mini-batch streams, the plain reference loss and the FLOP count. It
imports nothing of the program. ``adapters/<model>.py`` builds the
program's task around those inputs.
"""
from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

_LOADED: dict = {}


def load_file(path: str, name: str):
    """Import the Python file ``path`` once, as module ``name``."""
    path = os.path.abspath(path)
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def model(cfg: dict):
    kind = cfg["model"]
    return load_file(os.path.join(HERE, "models", f"{kind}.py"),
                     f"pb_model_{kind}")


def adapter(cfg: dict):
    kind = cfg["model"]
    return load_file(os.path.join(HERE, "adapters", f"{kind}.py"),
                     f"pb_adapter_{kind}")


#: the current run's inputs per cell, read by the task methods below
INPUTS: dict = {}


class BenchInputs:
    """Task methods that take weights, client data and mini-batch streams
    from the benchmark in place of the program's own. Mixed in ahead of a
    program task class, with ``cell`` and ``fault`` fields on the
    dataclass: ``fault == "half_batch"`` makes every local step's loss
    leave half of its rows out."""

    def init(self, key):
        return INPUTS[self.cell].pop("weights")

    def load_data(self, fed, seed):
        return INPUTS[self.cell]["data"]

    def make_batcher(self, dataset, batch_size, seed):
        ins = INPUTS[self.cell]
        return ins["model"].make_batcher(dataset, ins["cfg"], ins["traffic"])

    def loss(self, params, batch, prox=None):
        if self.fault == "half_batch":
            batch = INPUTS[self.cell]["model"].half_batch(batch)
        return super().loss(params, batch, prox)


def stage_inputs(cell: str, cfg: dict, traffic: dict, weights, data) -> None:
    INPUTS[cell] = {"weights": weights, "data": data, "cfg": cfg,
                    "traffic": traffic, "model": model(cfg)}
