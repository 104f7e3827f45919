"""The control (the reference without the inventory penalty, in the
program's place) comes out not correct where the program comes out correct.
The devices' counts are cut so that they bind at this size, as they do at
the cells' own sizes."""
from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

from bench import harness
from bench.control import run_control
from bench.tests.helpers import shrink

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SIZES = dict(rows=4, per_row=8, steps=30, counts=[6, 1])


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell):
    c = shrink(harness.load_cell(ROOT, cell), **SIZES)
    program = harness.run(c, 2**31 + 41, 0.6, False, time.time(), allow_cpu=True,
                          backend="pallas")
    control = run_control(c, 2**31 + 41, 0.6, allow_cpu=True, backend="pallas")
    assert program["correct"] is True
    assert control["correct"] is False
    assert control["checks"]["replay_mismatches"]["value"] > 0
