"""Hands an ``mlp`` configuration to the program: its ``PaperTask`` with
the benchmark's weights, client rows and evaluation batch in place of the
program's own. The forward pass, loss and training step are the
program's."""
from __future__ import annotations

import dataclasses

from repro.configs.paper_tasks import PaperTaskConfig
from repro.core.tasks import PaperTask

import pb_models


@dataclasses.dataclass(frozen=True)
class BenchPaperTask(pb_models.BenchInputs, PaperTask):
    cell: str = ""
    fault: str = ""

    def num_samples(self, dataset):
        return len(dataset[0])


def program_task(cell: str, cfg: dict, traffic: dict, fed, fault: str = ""):
    paper = PaperTaskConfig(
        name=cfg["name"], model="mlp", input_shape=(cfg["input_dim"],),
        num_classes=cfg["num_classes"], hidden=tuple(cfg["hidden"]),
        num_clients=traffic["clients"],
        samples_per_client=traffic["samples_per_client"], fed=fed)
    return BenchPaperTask(cfg=paper, cell=cell, fault=fault)
