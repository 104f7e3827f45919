"""The service cell's numbers: the request tail counts every request due in
the window, and each per-layer reader reads what the harness recorded."""
from __future__ import annotations

import asyncio
import math
import types
from pathlib import Path

import pytest

from bench import generator, harness, tracing

ROOT = Path(__file__).resolve().parents[2]
MIX = {"rate_hz": 40.0, "zipf_a": 0.0, "revisit": 0.5,
       "service": {"max_batch": 8, "max_wait_ms": 5}}


class _Service:
    """Stands in for PackingService: the k-th request raises, never
    returns, is slow or is answered at once, as ``script`` says."""

    def __init__(self, script, *_, **__):
        self.script, self.calls = script, 0

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return None

    async def pack(self, prob, seed):
        k, self.calls = self.calls, self.calls + 1
        what = self.script.get(k)
        if what == "fail":
            raise RuntimeError("planted failure")
        if what == "hang":
            await asyncio.Event().wait()
        if what == "slow":
            await asyncio.sleep(0.3)
        sol = types.SimpleNamespace(bins=[[0]], kinds=[0])
        return types.SimpleNamespace(solution=sol, cost=1, trace=[(0.0, 1)], iterations=1,
                                     params={"backend": "pallas", "interpret": True})

    def stats(self):
        return {"solved": 0, "batches": 0, "batch_occupancy": {"mean": 0.0},
                "coalesced": 0, "cache_hits_mem": 0, "hit_rate": 0.0}


def _serve(monkeypatch, script, seconds=1.0):
    import repro.serve

    monkeypatch.setattr(repro.serve, "PackingService",
                        lambda *a, **kw: _Service(script, *a, **kw))
    monkeypatch.setattr(harness, "GRACE_S", 0.5)
    cell = types.SimpleNamespace(traffic=MIX)
    env = harness.Env(cell, [object()] * 16, "sa-s", 1e12, {}, "pallas", 2**31 + 5)
    return harness.run_serve(env, seconds)


@pytest.mark.parametrize("extra_missing", [0, 1])
@pytest.mark.parametrize("q", [0.8, 0.9])
def test_request_tail_is_a_nearest_rank_with_missing_requests_at_infinity(
        monkeypatch, q, extra_missing):
    plan = generator.arrivals(2**31 + 5, 1.0, 16, MIX["rate_hz"], MIX["zipf_a"],
                              MIX["revisit"])
    n = len(plan)
    room = n - math.ceil(q * n)  # requests that may be missing with the tail finite
    assert room >= 2
    missing = room + extra_missing
    script = {k: ("fail" if k % 2 else "hang") for k in range(missing)}
    script[n - 1] = "slow"
    win = _serve(monkeypatch, script)
    assert win.attempted == n and win.failed == missing
    assert win.notes["requests"] == n
    name = f"request_p{round(100 * q)}_ms"
    tail = win.metrics[name]
    if extra_missing:  # the rank lands on a missing request
        assert tail is None and win.notes[name] == math.inf
    else:  # the rank lands on the slowest answer, timed from its due time
        assert tail == win.notes[name] >= 300.0
        assert win.notes["request_p50_ms"] < 300.0


def _view(**kw):
    return harness.RunView(**{"trace": None, "calls": [], "peaks": {}, "stats": None, **kw})


def _reader(name):
    return harness.metric_reader(ROOT, name)


def test_queue_wait_pairs_each_request_with_the_batch_that_solved_it():
    requests = [(10.0, 0, 5), (10.1, 0, 5), (10.2, 1, 6), (12.0, 0, 5), (12.5, 2, 7),
                (13.0, 3, 8)]
    batches = [(10.5, 11.0, {(0, 5), (1, 6)}),
               (12.6, 13.5, {(2, 7)})]
    # (0, 5) waited 0.5 and 0.4 s for the first batch, (1, 6) 0.3 s; the
    # second (0, 5) came after its batch had started (a hit), (3, 8) was
    # never solved, (2, 7) waited 0.1 s
    assert tracing.queue_waits(requests, batches) == pytest.approx([0.5, 0.4, 0.3, 0.1])
    read = _reader("queue_wait_ms.serve")
    assert read(_view(requests=requests, batches=batches)) == pytest.approx(300.0)
    assert read(_view(requests=requests[:1], batches=[])) is None
    assert read(_view()) is None


def test_queue_wait_takes_the_first_batch_after_the_due_time():
    # a task whose first batch failed is solved again by a later one
    batches = [(1.0, 2.0, {(0, 1)}), (3.0, 4.0, {(0, 1)}), (5.0, 6.0, {(0, 1)})]
    assert tracing.queue_waits([(2.5, 0, 1)], batches) == pytest.approx([0.5])


def test_service_counter_readers():
    stats = {"requests": 40, "batches": 16, "batch_occupancy": {"mean": 1.25},
             "hit_rate": 0.475}
    assert _reader("batch_occupancy.serve")(_view(stats=stats)) == 1.25
    assert _reader("hit_pct.serve")(_view(stats=stats)) == pytest.approx(47.5)
    idle = dict(stats, requests=0, batches=0)
    for name in ("batch_occupancy.serve", "hit_pct.serve"):
        assert _reader(name)(_view(stats=idle)) is None
        assert _reader(name)(_view()) is None


def _summary(spans, window_s=10.0):
    return tracing.TraceSummary(
        window_s=window_s, busy_s=0.02, n_chips=1, op_seconds={},
        kernel_seconds={"binpack_sa_step": 0.004, "binpack_fitness": 0.0},
        kernel_events={"binpack_sa_step": 2, "binpack_fitness": 0}, spans=spans, gaps=[])


def test_lane_busy_is_the_union_of_batches_over_the_window():
    batches = [(0.0, 2.0, {(0, 1)}), (1.0, 3.0, {(1, 2)}), (5.0, 6.0, {(0, 3)})]
    read = _reader("lane_busy_pct.serve")
    assert read(_view(batches=batches, elapsed_s=10.0)) == pytest.approx(40.0)
    assert read(_view(batches=[], elapsed_s=10.0)) is None
    assert read(_view()) is None


@pytest.mark.parametrize("metric", ["dispatch_us_per_call", "binpack_sa_step_roofline",
                                    "device_idle_pct"])
def test_device_readers_are_the_sweeps(metric):
    spans = [("bench.dispatch.sa_step", 0, 3e6), ("bench.dispatch.sa_step", 5e6, 6e6)]
    calls = [("binpack_sa_step", 16, 4, [[[1, 16384]], [[72, 4096]]])] * 2
    view = _view(trace=_summary(spans), calls=calls, peaks={"hbm_bytes_per_s": 819e9})
    got = _reader(f"{metric}.serve")(view)
    assert got is not None and got > 0
    assert got == _reader(f"{metric}.sweep")(view)


def test_an_untraced_run_notes_the_layers_it_reads_without_a_trace(capfd):
    from bench.tests.helpers import run_tiny

    line = run_tiny(ROOT, "table1-dse.serve", seed=2**31 + 11, seconds=0.5)
    assert line["correct"] is True
    notes = {ln[6:].split("=", 1)[0] for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("note: ")}
    assert {"queue_wait_ms.serve", "batch_occupancy.serve", "lane_busy_pct.serve",
            "hit_pct.serve"} <= notes
    assert not notes & {"dispatch_us_per_call.serve", "binpack_sa_step_roofline.serve",
                        "device_idle_pct.serve"}
