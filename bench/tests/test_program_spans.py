"""The program's ``repro.*`` spans in a profiled pack and sweep, and their
reduction (``bench/program_spans.py``).

On the CPU: a tiny heterogeneous ``pack`` and a tiny two-problem
``pack_sweep`` with ``backend="ref"``, profiled with program spans on inside
``bench.window`` / ``bench.solve`` and the harness's dispatch spans.

``bench/testdata/sa_fleet_spans_rn152_u50.xplane.pb`` was recorded on one
TPU v5e chip: a 40-step, 64-chain SA-S pack of RN152-W1A2 on the U50
inventory (solver seed 7, compiled Pallas kernel) with program spans on,
inside a ``bench.solve`` span and with the harness's dispatch spans.  The
solve took 1.93 s, 1.47 s of it one idle gap during the seeding."""
from __future__ import annotations

import math
from pathlib import Path

import jax
import pytest

from bench import harness, tracing
from bench.program_spans import (
    METRICS,
    program_metrics,
    program_share_pct,
    program_us_per_span,
    reduce_program,
    unspanned_pct,
)
from bench.tests.helpers import shrink
from repro import spans

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "bench" / "testdata"
STEPS = 12
DISPATCH = ("repro.dispatch.h2d", "repro.dispatch.launch", "repro.dispatch.d2h")


def _profiled(tmp, call_span, call):
    """``call()`` profiled with program spans and dispatch spans on."""
    dispatch, profile = tracing.DispatchSpans(), tracing.Profile(str(tmp))
    spans.enable(True)
    dispatch.install()
    profile.start()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation(call_span):
                out = call()
    finally:
        path = profile.stop()
        dispatch.remove()
        spans.enable(False)
    return path, out


def _setup(cell_name):
    cell = shrink(harness.load_cell(ROOT, cell_name), steps=STEPS)
    algorithm, max_seconds, solver = harness.solver_settings(cell.config)
    return harness.program_problems(cell.config), algorithm, max_seconds, solver


@pytest.fixture(scope="module")
def pack_trace(tmp_path_factory):
    import repro.core as c

    probs, algorithm, max_seconds, solver = _setup("rn152-u50.sa-fleet")

    def call():
        return c.pack(probs[0], algorithm, seed=3, max_seconds=max_seconds,
                      backend="ref", **solver)

    call()  # compiles outside the profile
    path, res = _profiled(tmp_path_factory.mktemp("pack"), "bench.solve", call)
    assert probs[0].n_kinds > 1
    return reduce_program(path), tracing.reduce_trace(path), solver, res


@pytest.fixture(scope="module")
def sweep_trace(tmp_path_factory):
    import repro.core as c

    probs, algorithm, max_seconds, solver = _setup("table1-dse.sweep")
    probs = [probs[0], probs[-1]]

    def call():
        return c.pack_sweep(probs, algorithm, seeds=[4, 5], max_seconds=max_seconds,
                            backend="ref", **solver)

    call()
    path, sw = _profiled(tmp_path_factory.mktemp("sweep"), "bench.sweep", call)
    return reduce_program(path), tracing.reduce_trace(path), solver, sw


def _inside(child, parents):
    _, s, e = child
    return any(ps <= s and e <= pe for _, ps, pe in parents)


def _of(t, name):
    return [x for x in t.spans + t.harness if x[0] == name]


def test_pack_span_counts(pack_trace):
    t, _, solver, res = pack_trace
    assert res.iterations == solver["n_chains"] * STEPS
    assert t.count("repro.sa.start") == t.count("repro.sa.seed") == 1
    assert t.count("repro.sa.finish") == 1
    assert t.count("repro.nfd.kinds") == solver["n_chains"]  # every chain fresh
    for name in ("repro.sa.propose", "repro.sa.accept") + DISPATCH:
        assert t.count(name) == STEPS, name


def test_sweep_span_counts(sweep_trace):
    t, _, solver, sw = sweep_trace
    groups = sw.n_groups
    assert t.count("repro.sa.start") == t.count("repro.sa.seed") == groups
    assert t.count("repro.sa.finish") == groups
    assert t.count("repro.nfd.kinds") == 2 * solver["n_chains"]
    for name in ("repro.sa.propose", "repro.sa.accept") + DISPATCH:
        assert t.count(name) == STEPS * groups, name


@pytest.mark.parametrize("which", ["pack_trace", "sweep_trace"])
def test_spans_nest(which, request):
    t, _, _, _ = request.getfixturevalue(which)
    call = "bench.solve" if which == "pack_trace" else "bench.sweep"
    parent = {
        "repro.sa.seed": "repro.sa.start",
        "repro.nfd.kinds": "repro.sa.seed",
        **{d: "bench.dispatch.sa_step" for d in DISPATCH},
    }
    for span in t.spans:
        assert _inside(span, _of(t, call)), span
        if span[0] in parent:
            assert _inside(span, _of(t, parent[span[0]])), span
    # propose and accept hold no kernel call: no span is open across a yield
    steps = _of(t, "repro.sa.propose") + _of(t, "repro.sa.accept")
    for d in _of(t, "bench.dispatch.sa_step"):
        assert not any(s < d[2] and d[1] < e for _, s, e in steps)


@pytest.mark.parametrize("which", ["pack_trace", "sweep_trace"])
def test_metrics_finite_and_in_range(which, request):
    t, summary, _, _ = request.getfixturevalue(which)
    m = program_metrics(t)
    assert set(m) == set(METRICS)
    assert all(v is not None and math.isfinite(v) for v in m.values()), m
    for k in ("seed_share_pct", "kind_assign_share_pct", "unspanned_pct"):
        assert 0 <= m[k] <= 100, (k, m[k])
    assert m["kind_assign_share_pct"] <= m["seed_share_pct"]
    assert all(m[k] > 0 for k in m if k.endswith("_us_per_step") or k.endswith("_per_call"))
    split = m["h2d_us_per_call"] + m["launch_us_per_call"] + m["d2h_us_per_call"]
    assert split <= tracing.dispatch_us_per_call(summary)


def test_old_reduction_reads_only_harness_spans(pack_trace):
    t, summary, _, _ = pack_trace
    assert all(n.startswith("bench.") for n, _, _ in summary.spans)
    assert sorted(summary.spans) == sorted(t.harness)


def test_helpers_by_hand(pack_trace):
    t, _, _, _ = pack_trace
    (s0, e0), = [(s, e) for _, s, e in _of(t, "repro.sa.seed")]
    assert program_share_pct(t, "repro.sa.seed") == pytest.approx(
        100 * (e0 - s0) / 1e9 / t.window_s)
    d = [e - s for _, s, e in _of(t, "repro.sa.accept")]
    assert program_us_per_span(t, "repro.sa.accept") == pytest.approx(sum(d) / len(d) / 1e3)
    assert program_share_pct(t, "repro.nope") is None
    assert program_us_per_span(t, "repro.nope") is None
    start = [(s, e) for _, s, e in _of(t, "repro.sa.start")]
    assert unspanned_pct(t) <= 100 * (1 - sum(e - s for s, e in start) / 1e9 / t.window_s)


def test_gaps_without_program_spans_label_as_before():
    path = str(TESTDATA / "sa_step_rn152_u50.xplane.pb")
    t = reduce_program(path, window_span="bench.solve")
    summary = tracing.reduce_trace(path, window_span="bench.solve")
    assert t.spans == [] and unspanned_pct(t) is None
    assert t.gaps == summary.gaps
    assert program_metrics(t) == {m: None for m in METRICS}


@pytest.fixture(scope="module")
def chip_trace():
    return reduce_program(str(TESTDATA / "sa_fleet_spans_rn152_u50.xplane.pb"),
                          window_span="bench.solve")


def test_chip_trace_span_counts(chip_trace):
    t = chip_trace
    assert t.window_s == pytest.approx(1.930348596)
    assert t.count("repro.sa.start") == t.count("repro.sa.seed") == 1
    assert t.count("repro.sa.finish") == 1
    assert t.count("repro.nfd.kinds") == 64
    for name in ("repro.sa.propose", "repro.sa.accept") + DISPATCH:
        assert t.count(name) == 40, name
    assert len([n for n, _, _ in t.harness if n == "bench.dispatch.sa_step"]) == 40


def test_chip_trace_longest_gap_is_seeding(chip_trace):
    label, seconds = chip_trace.gaps[0]
    assert label in ("repro.sa.seed", "repro.nfd.kinds")
    assert seconds > 0.5 * chip_trace.window_s
    m = program_metrics(chip_trace)
    assert m["seed_share_pct"] > 50
    summary = tracing.reduce_trace(str(TESTDATA / "sa_fleet_spans_rn152_u50.xplane.pb"),
                                   window_span="bench.solve")
    assert summary.kernel_events["binpack_sa_step"] == 40
    split = m["h2d_us_per_call"] + m["launch_us_per_call"] + m["d2h_us_per_call"]
    assert split <= tracing.dispatch_us_per_call(summary)
