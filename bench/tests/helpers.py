"""Tiny cells for CPU tests: the committed configurations and mixes, cut
to a graph and batches a test run can hold."""
from __future__ import annotations

import copy

from bench import cell, traffic


def tiny(config: str = "rmat-triangle-1chip", mix: str = "hub-window-b256",
         scale: int = 8, batch: int = 32, warmup: int = 3):
    cfg = copy.deepcopy(cell.load_config(config))
    cfg["scale"] = scale
    m = copy.deepcopy(traffic.load_mix(mix))
    m["batch"] = batch
    m["warmup_batches"] = warmup
    return cfg, m
