"""Program spans (``repro.spans``): off by default, JAX-free while off, and
never a change to an answer."""
from __future__ import annotations

import contextlib
import os
import subprocess
import sys
from pathlib import Path

import pytest

import repro.core as c
from repro import spans
from repro.core.problem import BRAM18, URAM288, Buffer, OCMInventory, PackingProblem

SRC = Path(__file__).resolve().parents[1] / "src"


def _hetero_problem():
    bufs = [Buffer(36, 4096, i % 4) for i in range(40)]
    return PackingProblem(bufs, ocm=OCMInventory((BRAM18, URAM288), (40, 64)),
                          max_items=4)


def _answer(res):
    return (res.cost, [list(b) for b in res.solution.bins],
            [int(k) for k in res.solution.kinds], [x for _, x in res.trace],
            res.iterations)


@contextlib.contextmanager
def _spans_on():
    spans.enable(True)
    try:
        yield
    finally:
        spans.enable(False)


def test_off_by_default_and_shared_no_op():
    assert spans.span("repro.sa.propose") is spans.span("repro.sa.accept")
    assert isinstance(spans.span("x"), contextlib.nullcontext)
    with _spans_on():
        import jax

        assert isinstance(spans.span("repro.sa.seed"), jax.profiler.TraceAnnotation)
    assert isinstance(spans.span("x"), contextlib.nullcontext)


@pytest.mark.parametrize("backend", ["python", "ref"])
@pytest.mark.parametrize("profiled", [False, True])
def test_pack_bit_identical_with_spans_on(backend, profiled, tmp_path):
    prob = _hetero_problem()
    kw = dict(seed=5, n_chains=4, max_iterations=15, backend=backend)
    off = _answer(c.pack(prob, "sa-s", **kw))
    with _spans_on():
        if profiled:
            import jax

            with jax.profiler.trace(str(tmp_path)):
                on = _answer(c.pack(prob, "sa-s", **kw))
        else:
            on = _answer(c.pack(prob, "sa-s", **kw))
    assert on == off


def test_sweep_bit_identical_with_spans_on():
    probs = [_hetero_problem(), c.get_problem("CNV-W1A1")]
    kw = dict(seeds=[1, 2], n_chains=4, max_iterations=10, backend="ref")
    off = [_answer(r) for r in c.pack_sweep(probs, "sa-s", **kw).results]
    with _spans_on():
        on = [_answer(r) for r in c.pack_sweep(probs, "sa-s", **kw).results]
    assert on == off


@pytest.mark.parametrize("problem, walks", [
    ("overflowing", True),  # BRAM18 over on every seed
    ("roomy", False),  # two kinds, neither over
    ("single-kind", False),
])
def test_kind_walk_span_once_per_overflowing_seed(problem, walks, monkeypatch):
    prob = {
        "overflowing": _hetero_problem(),
        "roomy": PackingProblem([Buffer(36, 4096, i % 4) for i in range(40)],
                                ocm=OCMInventory((BRAM18, URAM288), (1000, 64)),
                                max_items=4),
        "single-kind": c.get_problem("CNV-W1A1"),
    }[problem]
    names = []

    @contextlib.contextmanager
    def record(name):
        names.append(name)
        yield

    monkeypatch.setattr(spans, "_annotation", record)
    c.pack(prob, "sa-s", seed=3, n_chains=4, max_iterations=5, backend="python")
    assert names.count("repro.nfd.kinds") == 4  # one per fresh chain
    assert names.count("repro.nfd.kinds.walk") == (4 if walks else 0)


def test_python_backend_with_spans_off_imports_no_jax():
    code = (
        "import sys\n"
        "import repro.core as c\n"
        "from repro import spans\n"
        "with spans.span('repro.sa.start'):\n"
        "    pass\n"
        "r = c.pack(c.get_problem('CNV-W1A1', device='U50'), 'sa-s', seed=0,\n"
        "           n_chains=4, max_iterations=5, backend='python')\n"
        "assert r.solution.kinds is not None\n"
        "print('jax' in sys.modules)\n"
    )
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "False"


@pytest.mark.parametrize("sweep", [False, True])
def test_ga_mutate_span_once_per_generation(sweep, monkeypatch):
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    kw = dict(n_pop=8, max_generations=6, backend="ref", max_seconds=1e12,
              patience=10**12)

    def solve():
        if sweep:
            return [_answer(r) for r in
                    c.pack_sweep(probs, "ga-nfd", seeds=[1, 2], **kw).results]
        return [_answer(c.pack(probs[0], "ga-nfd", seed=1, **kw))]

    off = solve()
    names = []

    @contextlib.contextmanager
    def record(name):
        names.append(name)
        yield

    monkeypatch.setattr(spans, "_annotation", record)
    assert solve() == off
    assert names.count("repro.ga.mutate") == 6 * len(off)  # one per generation
