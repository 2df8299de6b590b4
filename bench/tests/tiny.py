"""Cells of BENCHMARK.json cut to a CPU test's size: the same harness,
kinds, references and metrics, at a tiny configuration and traffic."""
from __future__ import annotations

import dataclasses
import time

from bench import harness as H

TRAFFIC = {
    "finetune": {"batch": 2, "seq": 32, "steps_per_workflow": 4},
    "serve": {"requests_per_workflow": 2, "prompt_min": 4, "prompt_max": 12,
              "gen_len": 4, "max_len": 16, "rate_per_s": 8.0},
}


# The control's gaps grow with depth and with the tokens checked: at two
# layers a float8 reference of a served model lies within the chip's limit.
# The control's test of a serve mix keeps the cell's own depth, at a width
# of 256, and checks more tokens.
DEEP = {"serve": {"requests_per_workflow": 4, "gen_len": 16, "max_len": 28}}


def tiny_cell(name: str, deep: bool = False, **traffic) -> H.Cell:
    from repro.configs import reduced
    from repro.configs.base import ModelConfig
    cell = H.load_cell(name)
    full = ModelConfig(**cell.config["model"])
    cfg = reduced(full)
    if deep and cell.traffic["kind"] in DEEP:
        cfg = dataclasses.replace(cfg, num_layers=full.num_layers,
                                  d_model=256)
        traffic = {**DEEP[cell.traffic["kind"]], **traffic}
    cell.config = {**cell.config, "model": dataclasses.asdict(cfg)}
    cell.traffic = {**cell.traffic, **TRAFFIC[cell.traffic["kind"]],
                    **traffic}
    return cell


def tiny_run(cell: H.Cell, seed: int = 2 ** 31 + 11, seconds: float = 1.0,
             fault=None, trace: bool = False) -> H.Run:
    return H.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                 t_process=time.time(), fault=fault)
