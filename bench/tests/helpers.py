"""Runs a cell on the CPU at a tiny size, with the Pallas kernels
interpreted.  Only tests use this; ``bench/run.py`` always needs a TPU."""
from __future__ import annotations

import time

from bench import harness


def shrink(cell: harness.Cell, rows: int = 3, per_row: int = 5, steps: int = 12,
           counts=None, replay_all: bool = False) -> harness.Cell:
    """Every accelerator cut to its first ``rows`` shape rows of at most
    ``per_row`` buffers each; SA-S runs four chains of ``steps`` steps with
    an exchange every five, GA-NFD a population of eight for ``steps``
    generations.  ``counts`` replaces the RAM counts of every device with as
    many kinds (a one-kind device keeps its own); ``replay_all`` has the
    check replay every distinct task instead of a sample."""
    cfg = dict(cell.config)
    cfg["accelerators"] = {
        name: [[min(int(n), per_row), shape] for n, shape in acc[:rows]]
        for name, acc in cfg["accelerators"].items()
    }
    if "max_generations" in cfg:
        cfg["max_generations"] = steps
        cfg["solver"] = dict(cfg["solver"], n_pop=8)
    else:
        cfg["n_chains"], cfg["max_iterations"] = 4, steps
        cfg["solver"] = dict(cfg["solver"], exchange_every=5)
    if counts is not None:
        cfg["devices"] = {d: dict(v, counts=list(counts)) if len(v["kinds"]) == len(counts)
                          else v for d, v in cfg["devices"].items()}
    traffic = dict(cell.traffic)
    if replay_all:
        traffic["check_sample"] = 10**6
    if traffic["entry"] == "serve":
        traffic["rate_hz"] = 40.0
    return harness.Cell(cell.name, cfg, traffic, cell.chips, cell.end_to_end,
                        cell.per_layer, cell.root)


def run_tiny(root, name: str, seed: int = 1, seconds: float = 1.0,
             trace: bool = False, **sizes) -> dict:
    cell = shrink(harness.load_cell(root, name), **sizes)
    return harness.run(cell, seed, seconds, trace, time.time(),
                       allow_cpu=True, backend="pallas")
