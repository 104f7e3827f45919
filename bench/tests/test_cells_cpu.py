"""Every cell runs end to end on the CPU at a tiny size, Pallas interpreted,
and prints a result line of the contract's shape."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness
from bench.tests.helpers import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_on_the_cpu(cell):
    line = run_tiny(ROOT, cell, seed=2**31 + 11, seconds=0.6)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in harness.load_cell(ROOT, cell).end_to_end}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_spans_its_own_kernel_alone(cell, monkeypatch):
    """The dispatch spans see the cell's solver's kernel and no other: the
    SA cells never call the GA's fitness kernel."""
    from bench import tracing

    made = []

    class Spans(tracing.DispatchSpans):
        def __init__(self):
            super().__init__()
            made.append(self)

    monkeypatch.setattr(tracing, "DispatchSpans", Spans)
    line = run_tiny(ROOT, cell, seed=2**31 + 13, seconds=0.4, trace=True)
    assert line["correct"] is True
    kernel = "binpack_fitness" if cell.endswith(".ga") else "binpack_sa_step"
    assert made[0].calls and {c[0] for c in made[0].calls} == {kernel}
    layer = {m["name"] for m in harness.load_cell(ROOT, cell).per_layer}
    # on the CPU the trace has no device plane: the span readers read, the
    # device readers find nothing
    assert {n for n in layer if n.startswith(("host_share", "dispatch_us"))} <= set(
        line["metrics"])
    assert not {n for n in line["metrics"] if n.startswith(("device_idle", "binpack_"))}


def test_same_seed_same_inputs():
    from bench import generator

    a = generator.arrivals(2**31 + 3, 5.0, 16, 10.0, 1.2, 0.5)
    assert a == generator.arrivals(2**31 + 3, 5.0, 16, 10.0, 1.2, 0.5)
    assert a != generator.arrivals(2**31 + 4, 5.0, 16, 10.0, 1.2, 0.5)
    assert generator.SolverSeeds(5).take(4) == generator.SolverSeeds(5).take(4)


def test_nearest_rank():
    from bench.generator import nearest_rank

    assert nearest_rank([3, 1, 2, 4], 0.5) == 2
    assert nearest_rank(range(1, 101), 0.95) == 95
    assert nearest_rank([7], 0.95) == 7
