"""BENCHMARK.json resolves to its files by name, keeps to the shape the
check reads, and takes a new cell from data files alone."""
from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness
from bench.tests.helpers import run_tiny

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert (ROOT / BENCH["command"][1]).is_file()
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.chips == 1
    assert c.traffic["entry"] in harness.LOOPS
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"} and len(c.end_to_end) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(ROOT, m["name"]))
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    probs = harness.reference_problems(c.config)
    assert len(probs) == len(c.config["problems"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_names_its_reductions(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert set(entry["reduced"]) <= set(cfg)
    assert set(entry["reduced"]) == set(cfg["reduced_from_source"])
    sizes = {p.name: p.n for p in harness.reference_problems(cfg)}
    if "RN152-W1A2@U50" in sizes:  # Table 1 with the documented RN152 scaling
        assert sizes["RN152-W1A2@U50"] == 2253


SERVE_MIX = {"entry": "serve", "loop": "open", "rate_hz": 2.0, "zipf_a": 0.0,
             "revisit": 0.5, "service": {"max_batch": 8, "max_wait_ms": 5.0},
             "check_sample": 4}
OCCUPANCY = '''def read(run):
    if not run.stats or not run.stats["batches"]:
        return None
    return run.stats["batch_occupancy"]["mean"]
'''


@pytest.mark.parametrize("entry", ["pack", "serve", "ga-nfd"])
def test_a_cell_made_of_data_files_runs_without_a_harness_edit(tmp_path, entry):
    """A later PR adds a cell with a traffic file, metric readers and
    entries only: a closed loop of single solves, an open loop of service
    requests, or a GA-NFD configuration of its own."""
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"table1-dse.{entry}-mix"
    config = "table1-zu7ev-u50"
    if entry == "ga-nfd":
        # CNV-W1A1 (Table 1) on BRAM18 alone, GA-NFD at the packer's defaults
        cfg = json.loads((ROOT / "bench" / "configs" / "rn152-w1a2-bram18.json").read_text())
        table1 = json.loads((ROOT / "bench" / "configs" / "table1-zu7ev-u50.json").read_text())
        config = "cnv-w1a1-bram18"
        cfg.update(name=config, accelerators={"CNV-W1A1": table1["accelerators"]["CNV-W1A1"]},
                   problems=[{"accelerator": "CNV-W1A1", "device": "BRAM18-unbounded"}],
                   max_generations=5, solver=dict(cfg["solver"], n_pop=6))
        (tmp_path / "bench" / "configs" / f"{config}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": config, "source": "https://arxiv.org/abs/2003.12449",
                                 "file": f"bench/configs/{config}.json", "reduced": [],
                                 "why": "a test configuration"})
        mix = json.loads((ROOT / "bench" / "traffic" / "single-pack-ga.json").read_text())
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"].endswith(".ga") or m["name"] == "solve_s":
                m["workloads"].append(name)
        want = {"solve_s", "setup_s"}
    elif entry == "pack":
        mix = json.loads((ROOT / "bench" / "traffic" / "single-pack-sa.json").read_text())
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"].endswith("solve") or m["name"] == "solve_s":
                m["workloads"].append(name)
        want = {"solve_s", "setup_s"}
    else:
        mix = SERVE_MIX
        (tmp_path / "bench" / "metrics" / "serve_batch_occupancy.py").write_text(OCCUPANCY)
        for m in bench["end_to_end"]:
            if m["name"] == "request_p80_ms":
                m["workloads"].append(name)
        bench["per_layer"].append({"name": "serve_batch_occupancy", "unit": "requests/batch",
                                   "better": "higher", "source": "program_counter",
                                   "layer": "batcher", "moves": "request_p80_ms",
                                   "workloads": [name]})
        want = {"request_p80_ms", "setup_s"}
    (tmp_path / "bench" / "traffic" / f"{entry}-mix.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": f"{entry}-mix", "chips": 1, "why": "a test cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line = run_tiny(tmp_path, name, seed=7, seconds=0.5)
    assert line["correct"] is True
    assert set(line["metrics"]) == want
    if entry == "serve":
        traced = run_tiny(tmp_path, name, seed=8, seconds=0.5, trace=True)
        assert set(traced["metrics"]) == {"serve_batch_occupancy"}


@pytest.mark.parametrize("algorithm,refusal", [("ga-x", "no solver file"),
                                               ("ga-nfd", "one-kind devices only")])
def test_a_solver_that_cannot_be_checked_is_refused_before_a_run(algorithm, refusal):
    """An algorithm with no solver file, or GA-NFD on the U50's two kinds."""
    cell = harness.load_cell(ROOT, "rn152-u50.sa-fleet")
    cell.config = dict(cell.config, solver=dict(cell.config["solver"], algorithm=algorithm))
    with pytest.raises(harness.CellError, match=refusal):
        harness.run(cell, 1, 0.1, False, 0.0, allow_cpu=True)
