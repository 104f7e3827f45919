"""``chip_smoke.py --cpu-rehearsal`` in-process: the script's phases run at
their tiny size on the CPU, with the Pallas kernels interpreted, so the
chip smoke run cannot rot between chip runs."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

PHASES = ("ga", "sa", "sweep", "portfolio", "service")


def test_cpu_rehearsal_phases_bit_identical(capsys):
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in PHASES:
        (line,) = [ln for ln in lines if ln.startswith(f"[{phase}] ")]
        assert "backend=pallas interpret=True" in line
        assert "parity=bit-identical" in line
    assert "fused=True" in next(ln for ln in lines if ln.startswith("[portfolio]"))
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }}


def test_smoke_refuses_a_host_without_a_chip(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
