"""The plain reference answers what the program answers, and its audit
finds what is wrong with an answer.  The program runs here with
``backend="python"`` (host numpy, no kernel), which its own tests pin
bit-identical to the Pallas path."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness
from bench.reference import audit, canonical, replay_ga_nfd, replay_sa_s

ROOT = Path(__file__).resolve().parents[2]
TABLE1 = json.loads((ROOT / "bench" / "configs" / "table1-zu7ev-u50.json").read_text())
HYPER = {k: TABLE1["solver"][k] for k in (
    "sa_t0", "sa_rc", "p_adm_w", "p_adm_h", "swap_moves", "ladder_min",
    "ladder_max", "p_kind")}


BRAM18 = json.loads((ROOT / "bench" / "configs" / "rn152-w1a2-bram18.json").read_text())
GA = {k: BRAM18["solver"][k] for k in (
    "n_tour", "p_mut", "p_adm_w", "p_adm_h", "nfd_threshold", "nfd_extra_frac",
    "nfd_max_bins", "layer_weight")}


def _pair(index):
    return harness.program_problems(TABLE1)[index], harness.reference_problems(TABLE1)[index]


@pytest.mark.parametrize("index,seed,chains,steps,exchange", [
    (0, 3, 4, 60, 16),     # CNV-W1A1 on ZU7EV, exchanges on
    (5, 2**31 + 7, 8, 120, 256),  # RN50-W1A2 on ZU7EV: inventory binds
    (10, 11, 2, 90, 32),   # Tincy-YOLO on U50, two chains
    (12, 5, 8, 300, 256),  # ReBNet on U50: one exchange at step 256
])
def test_replay_matches_the_program(index, seed, chains, steps, exchange):
    import repro.core as c

    prob, ref = _pair(index)
    res = c.pack(prob, "sa-s", seed=seed, n_chains=chains, max_iterations=steps,
                 backend="python", max_seconds=1e12, patience=10**12,
                 exchange_every=exchange, **HYPER)
    want = replay_sa_s(ref, seed, chains, steps, exchange_every=exchange, **HYPER)
    assert int(res.cost) == want["cost"]
    assert [x for _, x in res.trace] == want["trace"]
    assert res.iterations == want["iterations"] == chains * steps
    assert canonical(res.solution.bins, res.solution.kinds) == canonical(
        want["bins"], want["kinds"])
    assert audit(ref, want["bins"], want["kinds"], want["cost"], want["trace"][-1],
                 want["iterations"], chains * steps) == []


@pytest.mark.parametrize("backend", ["python", "pallas"])
@pytest.mark.parametrize("seed", [1, 7, 2**31 + 5])
def test_ga_nfd_replay_matches_the_program(seed, backend):
    """RN152-W1A2's first four shape rows, at most 24 buffers each, on
    BRAM18 alone; a population of 12 for 40 generations."""
    import repro.core as c

    cfg = dict(BRAM18, accelerators={
        "RN152-W1A2": [[min(n, 24), shape] for n, shape in
                       BRAM18["accelerators"]["RN152-W1A2"][:4]]})
    prob, ref = harness.program_problems(cfg)[0], harness.reference_problems(cfg)[0]
    res = c.pack(prob, "ga-nfd", seed=seed, n_pop=12, max_generations=40,
                 backend=backend, max_seconds=1e12, patience=10**12, **GA)
    want = replay_ga_nfd(ref, seed, 12, 40, **GA)
    assert res.params["backend"] == backend
    assert int(res.cost) == want["cost"]
    assert [x for _, x in res.trace] == want["trace"] and len(want["trace"]) > 2
    assert res.iterations == want["iterations"] == 40
    assert canonical(res.solution.bins, res.solution.kinds) == canonical(
        want["bins"], want["kinds"])
    assert audit(ref, want["bins"], want["kinds"], want["cost"], want["trace"][-1],
                 want["iterations"], 40) == []


def test_audit_finds_each_broken_guarantee():
    _, ref = _pair(0)
    ans = replay_sa_s(ref, 1, 4, 20, **HYPER)
    bins, kinds, cost, last = ans["bins"], ans["kinds"], ans["cost"], ans["trace"][-1]
    ok = dict(cost=cost, trace_last=last, iterations=80, expected_iterations=80)
    assert audit(ref, bins, kinds, **ok) == []
    assert audit(ref, bins[1:], kinds[1:], **ok)  # buffers left out
    k = next(k for k in range(2, len(bins)) if sum(map(len, bins[:k])) > ref.max_items)
    merged = [sum(bins[:k], [])] + bins[k:]  # a bin over max_items
    assert any("more than" in f for f in audit(ref, merged, kinds[k - 1:], **ok))
    assert audit(ref, bins, kinds, **dict(ok, cost=cost + 1))
    flipped = [1 - kinds[0]] + kinds[1:]
    assert audit(ref, bins, flipped, **ok)  # kind changed, cost kept
    assert audit(ref, bins, kinds, **dict(ok, iterations=79))
