"""Hands a ``dense_lm`` configuration to the program: its ``ArchTask``
with the benchmark's weights, token streams and evaluation batch in place
of the program's own. The forward pass, loss and training step are the
program's."""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.tasks import ArchTask

import pb_models


@dataclasses.dataclass(frozen=True)
class BenchArchTask(pb_models.BenchInputs, ArchTask):
    cell: str = ""
    fault: str = ""


def model_config(cfg: dict) -> ModelConfig:
    d, h, kv, hd, f, v, n = pb_models.model(cfg).dims(cfg)
    window = cfg.get("sliding_window") or 0
    return ModelConfig(
        arch_id=cfg["name"], family="dense", source=cfg["source"],
        num_layers=n, d_model=d, num_heads=h, num_kv_heads=kv, head_dim=hd,
        d_ff=f, vocab_size=v, sliding_window=window,
        long_context_window=window or 4096, rope_theta=cfg["rope_theta"],
        activation="swiglu", norm="rmsnorm",
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"], param_dtype=cfg["param_dtype"])


def program_task(cell: str, cfg: dict, traffic: dict, fed, fault: str = ""):
    shape = ShapeConfig("bench", traffic["seq_len"], traffic["sequences"],
                        "train")
    chunk = cfg["attention_chunk"]
    return BenchArchTask(cfg=model_config(cfg), shape=shape, q_chunk=chunk,
                         kv_chunk=chunk, fed_cfg=fed, cell=cell, fault=fault)
