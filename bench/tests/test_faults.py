"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted where the cell's solver does the work: SA-S in the
kernel-dispatch layer its annealing steps pass through and in its fleet's
finish, GA-NFD in its generation's mutations, its fitness dispatch and its
finish.  The rest of the run (set-up, window, check) is the benchmark's
own, on the CPU with the kernels interpreted.  No cell spans chips, so
there is no exchange between chips to leave out."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench.tests.helpers import run_tiny

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
ALGORITHM = {w["name"]: json.loads((ROOT / c["file"]).read_text())["solver"]["algorithm"]
             for w in BENCH["workloads"] for c in BENCH["configs"]
             if c["name"] == w["config"]}


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


def _plant_sa(monkeypatch, fault):
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

    wrap = {"state_unchanged": _state_unchanged, "half_batch": _half_batch}[fault]
    monkeypatch.setattr(ops, "sa_step_deltas", wrap(ops.sa_step_deltas))


def _plant_ga(monkeypatch, fault):
    from repro.core.ga import GeneticPacker

    if fault == "state_unchanged":
        # a generation that leaves its population as it was: nothing mutates
        # (the fitness kernel holds no state of the GA's to leave unchanged)
        monkeypatch.setattr(GeneticPacker, "_mutation_phase", lambda self, run: [])
    elif fault == "half_batch":
        from repro.kernels.binpack_fitness import ops

        orig = ops.population_costs

        def costs(*a, **kw):  # only the first half of the population costed
            out = np.array(orig(*a, **kw))
            out[len(out) // 2:] = 0
            return out

        monkeypatch.setattr(ops, "population_costs", costs)
    else:
        # the answer altered where it is produced: the last buffer of the
        # best packing's first shared bin moves to a bin of its own, the
        # reported cost kept
        from repro.core.problem import Solution

        orig = GeneticPacker._finish_run

        def finish(self, run):
            res = orig(self, run)
            bins = [list(b) for b in res.solution.bins]
            j = next(j for j, b in enumerate(bins) if len(b) > 1)
            bins.append([bins[j].pop()])
            res.solution = Solution(res.solution.problem, bins)
            return res

        monkeypatch.setattr(GeneticPacker, "_finish_run", finish)


PLANT = {"sa-s": _plant_sa, "ga-nfd": _plant_ga}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "state_unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    PLANT[ALGORITHM[cell]](monkeypatch, fault)
    # the devices' counts cut so that they bind at this size, as they do at
    # the cells' own sizes: a fault then moves the answers it touches
    line = run_tiny(ROOT, cell, seed=2**31 + 29, seconds=0.6, rows=4, per_row=8,
                    steps=30, counts=[6, 1], replay_all=True)
    assert line["correct"] is False
    found = line["checks"]
    assert found["audit_failures"]["value"] + found["replay_mismatches"]["value"] > 0
