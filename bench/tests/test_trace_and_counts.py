"""The trace reduction, the peak table and the op and byte counts.

``bench/testdata/sa_step_rn152_u50.xplane.pb`` was recorded on one TPU v5e
chip: a 40-step, 64-chain SA-S pack of RN152-W1A2 on the U50 inventory
under ``jax.profiler``, with a ``bench.solve`` span around the pack and a
``bench.dispatch.sa_step`` span around each kernel-dispatch call.  Its
device plane holds 40 ``sa_step_deltas_kinds_pallas`` custom-calls,
66 111 ns in all, and 40 ``jit_sa_step_deltas_kinds_pallas`` modules."""
from __future__ import annotations

import types
from pathlib import Path

import numpy as np
import pytest

from bench import opcount, tracing
from bench.peaks import peak

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "sa_step_rn152_u50.xplane.pb"
BRAM18 = [(1, 16384), (2, 8192), (4, 4096), (9, 2048), (18, 1024), (36, 512)]
URAM288 = [(72, 4096)]


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce_trace(str(TRACE), window_span="bench.solve")


def test_kernel_time_and_events(summary):
    assert summary.n_chips == 1
    assert summary.kernel_events == {"binpack_sa_step": 40, "binpack_fitness": 0}
    assert summary.kernel_seconds["binpack_sa_step"] == pytest.approx(66111e-9)
    assert summary.op_seconds["sa_step_deltas_kinds_pallas"] == pytest.approx(66111e-9)


def test_busy_idle_and_spans(summary):
    assert 0 < summary.busy_s < summary.window_s
    assert summary.window_s == pytest.approx(1.885492537)
    # busy is a union: never more than the summed op time
    assert summary.busy_s <= sum(summary.op_seconds.values()) + 1e-12
    assert len(summary.span_seconds(tracing.DISPATCH)) == 40
    idle = tracing.device_idle_pct(summary)
    assert idle == pytest.approx(100 * (1 - summary.busy_s / summary.window_s))
    gaps = [s for _, s in summary.gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert {n for n, _ in summary.gaps} <= {"outside harness spans",
                                            "bench.dispatch.sa_step"}


def test_shares_from_the_trace(summary):
    d = summary.span_seconds(tracing.DISPATCH)
    assert tracing.dispatch_us_per_call(summary) == pytest.approx(1e6 * sum(d) / 40)
    assert tracing.host_share_pct(summary) == pytest.approx(
        100 * (summary.window_s - sum(d)) / summary.window_s)
    calls = [("binpack_sa_step", 64, 4, [BRAM18, URAM288])] * 40
    share = tracing.hbm_roofline_pct(summary, calls, "binpack_sa_step",
                                     peak("TPU v5 lite"))
    assert share == pytest.approx(100 * (40 * 6400 / 819e9) / 66111e-9)
    assert 0 < share < 100
    assert tracing.hbm_roofline_pct(None, calls, "binpack_sa_step", {}) is None


def test_short_op_names():
    assert tracing.short_op_name("%copy-done.4 = s32[64,4] copy-done(x)") == "copy-done"
    assert tracing.short_op_name(
        "%sa_step_deltas_kinds_pallas.1 = s32[64,1] custom-call(...)"
    ) == "sa_step_deltas_kinds_pallas"


def test_peaks_are_published_and_unknown_kinds_fail():
    p = peak("TPU v5 lite")
    assert (p["hbm_bytes_per_s"], p["bf16_flops_per_s"], p["int8_ops_per_s"]) == (
        819e9, 197e12, 393e12)
    with pytest.raises(KeyError):
        peak("TPU v4")


def test_counts_at_hand_computed_shapes():
    # one slot on BRAM18 alone: 6 modes x 6 ops + mask 2 = 38
    assert opcount.slot_cost_ops([BRAM18]) == 38
    # with URAM288 beside it: (36 + 3) + (6 + 3) + 2 = 50
    assert opcount.slot_cost_ops([BRAM18, URAM288]) == 50
    # SA step, 64 chains x 4 touched slots, kind lanes: 6 int32 fields in,
    # one int32 delta out per chain
    assert opcount.sa_step(64, 4, [BRAM18, URAM288]) == (256 * (2 * 50 + 2),
                                                          256 * 6 * 4 + 64 * 4)
    assert opcount.sa_step(8, 4, [BRAM18]) == (32 * 78, 32 * 16 + 32)
    # fitness, population 75 x 1200 bins: w, h, k in, a cost per bin out
    assert opcount.fitness(75, 1200, [BRAM18, URAM288]) == (90000 * 50, 90000 * 16)
    assert opcount.fitness(50, 10, [BRAM18]) == (500 * 38, 500 * 12)
    assert opcount.portfolio_step(75, 1200, 64, 4, [BRAM18, URAM288]) == (
        90000 * 50 + 256 * 102, 90000 * 16 + 6400)


def test_fitness_calls_are_spanned_with_their_shapes():
    from repro.kernels.binpack_fitness import ops

    orig = ops.population_costs
    spans = tracing.DispatchSpans()
    spans.install()
    try:
        w = np.ones((2, 5, 7), dtype=np.int32)  # a problem axis: one flattened call
        ops.population_costs(w, w, backend="ref")
        ops.population_costs(w[0], w[0], backend="ref", kinds=np.zeros_like(w[0]),
                             kind_tables=((1, tuple(BRAM18)), (16, tuple(URAM288))))
    finally:
        spans.remove()
    assert ops.population_costs is orig
    assert spans.calls == [("binpack_fitness", 10, 7, [BRAM18]),
                           ("binpack_fitness", 5, 7, [BRAM18, URAM288])]


def test_fitness_roofline_counts_its_calls():
    t = types.SimpleNamespace(kernel_events={"binpack_fitness": 2},
                              kernel_seconds={"binpack_fitness": 4e-6})
    calls = [("binpack_fitness", 75, 2253, [BRAM18])] * 2 + [
        ("binpack_sa_step", 64, 4, [BRAM18, URAM288])]
    share = tracing.hbm_roofline_pct(t, calls, "binpack_fitness", peak("TPU v5 lite"))
    assert share == pytest.approx(100 * (2 * 75 * 2253 * 12 / 819e9) / 4e-6)
