"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the kernel-dispatch layer that every cell's
annealing steps pass through; the rest of the run (set-up, window, check)
is the benchmark's own, on the CPU with the kernels interpreted.  No cell
spans chips, so there is no exchange between chips to leave out."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench.tests.helpers import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _state_unchanged(orig):
    def step(*a, **kw):  # every move priced out of reach: no chain moves
        return np.full(len(orig(*a, **kw)), 10**9, dtype=np.int64)
    return step


def _half_batch(orig):
    def step(*a, **kw):  # only the first half of the rows costed
        out = np.array(orig(*a, **kw))
        out[len(out) // 2:] = 0
        return out
    return step


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch}


def _plant(monkeypatch, fault):
    if fault == "answer_altered":
        # the fleet's answer altered where it is produced: bin 0 of every
        # best packing moves to the next RAM kind, its reported cost kept
        from repro.core.sa import SimulatedAnnealingPacker

        orig = SimulatedAnnealingPacker._block_finish

        def finish(self, st):
            outs = orig(self, st)
            for out in outs:
                kinds = out.best.kinds.copy()
                kinds[0] = (kinds[0] + 1) % out.best.problem.n_kinds
                out.best = type(out.best)(out.best.problem, out.best.bins, kinds)
            return outs

        monkeypatch.setattr(SimulatedAnnealingPacker, "_block_finish", finish)
        return
    from repro.kernels.binpack_sa_step import ops

    monkeypatch.setattr(ops, "sa_step_deltas", FAULTS[fault](ops.sa_step_deltas))


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    _plant(monkeypatch, fault)
    # the devices' counts cut so that they bind at this size, as they do at
    # the cells' own sizes: a fault then moves the answers it touches
    line = run_tiny(ROOT, cell, seed=2**31 + 29, seconds=0.6, rows=4, per_row=8,
                    steps=30, counts=[6, 1], replay_all=True)
    assert line["correct"] is False
    found = line["checks"]
    assert found["audit_failures"]["value"] + found["replay_mismatches"]["value"] > 0
