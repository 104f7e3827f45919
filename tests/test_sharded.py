"""Mesh-sharded fleet execution (PR 8): parity + resume-across-shard-counts.

Two sharding mechanisms, both pure execution-shape knobs:

* ``mesh=`` — the batched kernels row-shard every step over a 1-D
  ``("prob",)`` device mesh via ``shard_map`` (exact integer arithmetic, so
  bit-identical by construction).  Kernel- and engine-level mesh parity
  tests need >= 2 devices and skip otherwise; the CI sharded-smoke lane
  runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
* ``n_shards=`` — ``pack_sweep`` / ``pack_portfolio`` split each batched
  group into contiguous sub-fleets advanced concurrently on threads.
  Bit-parity holds because per-problem trajectories are fleet-composition-
  independent (each live problem consumes only its own RNG stream; frozen
  problems never draw) — these tests run on any host.

Checkpoints are cut in a canonical merged layout identical to the
unsharded snapshot, so a crashed sharded run must resume bit-identically
at ANY other shard count — pinned here with the ``tests/faultinject.py``
crash harness, both directions, for sweeps and portfolios
(docs/DESIGN.md section 14).
"""
from __future__ import annotations

import numpy as np
import pytest

import repro.core as c
from repro.core import pack_portfolio, pack_sweep
from repro.core.dse import shard_chunks
from repro.core.problem import (
    BRAM18,
    URAM288,
    Buffer,
    OCMInventory,
    PackingProblem,
)

from faultinject import SimulatedCrash, crash_at


def _n_devices() -> int:
    import jax

    return len(jax.devices())


def _mesh_or_skip(k: int):
    if _n_devices() < k:
        pytest.skip(f"needs {k} devices (CI sharded lane forces 8)")
    from repro.launch.mesh import make_sweep_mesh

    return make_sweep_mesh(k)


def _problem(seed: int, hetero: bool = False) -> PackingProblem:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(12, 30))
    bufs = [
        Buffer(width=int(rng.integers(1, 80)), depth=int(rng.integers(1, 40_000)),
               layer=int(rng.integers(0, 5)))
        for _ in range(n)
    ]
    ocm = (
        OCMInventory((BRAM18, URAM288), (n * 3, 8), name=f"dev{seed}")
        if hetero else None
    )
    return PackingProblem(bufs, max_items=4, name=f"sh{seed}", ocm=ocm)


def _record(sw) -> list[tuple]:
    return [
        (r.cost, r.solution.state_dict(), r.iterations,
         [cc for _, cc in r.trace])
        for r in sw.results
    ]


_KW = dict(max_seconds=1e9, patience=10**9)
_SA = dict(_KW, backend="python", max_iterations=400, n_chains=4)
_GA = dict(_KW, backend="ref", max_generations=8, n_pop=10)


# ------------------------------------------------------------- shard chunking
def test_shard_chunks_contiguous_and_balanced():
    assert shard_chunks(7, 3) == [[0, 1, 2], [3, 4], [5, 6]]
    assert shard_chunks(4, 8) == [[0], [1], [2], [3]]  # capped at n
    assert shard_chunks(5, 1) == [[0, 1, 2, 3, 4]]
    for n, k in ((13, 4), (8, 8), (9, 2)):
        chunks = shard_chunks(n, k)
        assert [j for ch in chunks for j in ch] == list(range(n))
        sizes = [len(ch) for ch in chunks]
        assert max(sizes) - min(sizes) <= 1


def test_make_sweep_mesh_validation():
    from repro.launch.mesh import make_sweep_mesh

    with pytest.raises(ValueError):
        make_sweep_mesh(0)
    with pytest.raises(RuntimeError, match="host_platform_device_count"):
        make_sweep_mesh(_n_devices() + 1)
    mesh = make_sweep_mesh(1)
    assert mesh.axis_names == ("prob",) and mesh.shape["prob"] == 1


# ------------------------------------------------------- kernel mesh parity
def test_kernel_mesh_parity():
    mesh = _mesh_or_skip(2)
    from repro.kernels.binpack_fitness.ops import population_costs
    from repro.kernels.binpack_sa_step.ops import sa_step_deltas

    rng = np.random.default_rng(0)
    W = rng.integers(0, 40, size=(7, 6))
    H = rng.integers(0, 9000, size=(7, 6))
    base = np.asarray(population_costs(W, H, backend="ref"))
    shrd = np.asarray(population_costs(W, H, backend="ref", mesh=mesh))
    np.testing.assert_array_equal(base, shrd)

    ow = rng.integers(0, 40, size=(5, 3))
    oh = rng.integers(0, 9000, size=(5, 3))
    nw = rng.integers(0, 40, size=(5, 3))
    nh = rng.integers(0, 9000, size=(5, 3))
    d0 = sa_step_deltas(ow, oh, nw, nh, backend="ref")
    d1 = sa_step_deltas(ow, oh, nw, nh, backend="ref", mesh=mesh)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_portfolio_step_kernel_mesh_parity():
    mesh = _mesh_or_skip(2)
    from repro.kernels.binpack_portfolio_step.ops import portfolio_step

    rng = np.random.default_rng(1)
    W = rng.integers(0, 40, size=(3, 8, 5))
    H = rng.integers(0, 9000, size=(3, 8, 5))
    ow = rng.integers(0, 40, size=(6, 2))
    oh = rng.integers(0, 9000, size=(6, 2))
    nw = rng.integers(0, 40, size=(6, 2))
    nh = rng.integers(0, 9000, size=(6, 2))
    t0, d0 = portfolio_step(W, H, ow, oh, nw, nh, backend="ref")
    t1, d1 = portfolio_step(W, H, ow, oh, nw, nh, backend="ref", mesh=mesh)
    np.testing.assert_array_equal(t0, t1)
    np.testing.assert_array_equal(d0, d1)


# --------------------------------------------------- sweep n_shards parity
@pytest.mark.parametrize("n_shards", [2, 3, 8])
def test_sweep_sa_n_shards_bit_identical(n_shards):
    probs = [_problem(s) for s in (11, 12, 13, 14, 15)]
    base = pack_sweep(probs, "sa-s", seed=3, **_SA)
    shrd = pack_sweep(probs, "sa-s", seed=3, n_shards=n_shards, **_SA)
    assert _record(shrd) == _record(base)
    assert shrd.params["n_shards"] == n_shards


def test_sweep_sa_n_shards_hetero_bit_identical():
    probs = [_problem(s, hetero=True) for s in (21, 22, 23)]
    base = pack_sweep(probs, "sa-s", seed=1, **_SA)
    shrd = pack_sweep(probs, "sa-s", seed=1, n_shards=3, **_SA)
    assert _record(shrd) == _record(base)


def test_sweep_ga_n_shards_bit_identical():
    probs = [_problem(s) for s in (31, 32, 33, 34)]
    base = pack_sweep(probs, "ga-nfd", seed=2, **_GA)
    shrd = pack_sweep(probs, "ga-nfd", seed=2, n_shards=3, **_GA)
    assert _record(shrd) == _record(base)


def test_sweep_n_shards_validation():
    with pytest.raises(ValueError, match="n_shards"):
        pack_sweep([_problem(1)], "sa-s", n_shards=0, **_SA)


# ------------------------------------------------------- sweep mesh parity
def test_sweep_sa_mesh_bit_identical():
    mesh = _mesh_or_skip(2)
    probs = [_problem(s) for s in (41, 42, 43)]
    kw = dict(_KW, backend="ref", max_iterations=100, n_chains=3)
    base = pack_sweep(probs, "sa-s", seed=5, **kw)
    shrd = pack_sweep(probs, "sa-s", seed=5, mesh=mesh, **kw)
    assert _record(shrd) == _record(base)
    # mesh + n_shards > 1: sub-fleets pinned round-robin to the devices
    pinned = pack_sweep(probs, "sa-s", seed=5, mesh=mesh, n_shards=2, **kw)
    assert _record(pinned) == _record(base)


def test_sweep_pinned_shards_spread_over_devices(monkeypatch):
    """``n_shards > 1`` with a mesh pins each sub-fleet's kernel calls to
    its own device through the thread-local ``jax.default_device``: the
    kernel outputs land on every mesh device, not all on device 0."""
    mesh = _mesh_or_skip(2)
    from repro.kernels.binpack_sa_step import kernel

    k = mesh.shape["prob"]
    seen: set = set()
    kernel_fn = kernel.sa_step_deltas_pallas

    def spy(*args):
        out = kernel_fn(*args)
        seen.update(out.devices())
        return out

    probs = [_problem(s) for s in range(101, 101 + k)]
    kw = dict(_KW, backend="pallas", max_iterations=12, n_chains=2)
    base = pack_sweep(probs, "sa-s", seed=5, **kw)
    monkeypatch.setattr(kernel, "sa_step_deltas_pallas", spy)
    pinned = pack_sweep(probs, "sa-s", seed=5, mesh=mesh, n_shards=k, **kw)
    assert _record(pinned) == _record(base)
    assert seen == set(mesh.devices.flat)


def test_sweep_ga_mesh_bit_identical():
    mesh = _mesh_or_skip(2)
    probs = [_problem(s) for s in (51, 52, 53)]
    kw = dict(_GA, max_generations=6)
    base = pack_sweep(probs, "ga-nfd", seed=4, **kw)
    shrd = pack_sweep(probs, "ga-nfd", seed=4, mesh=mesh, **kw)
    assert _record(shrd) == _record(base)


# --------------------------------------------------------- portfolio parity
_PF = dict(
    _KW, max_iterations=384, max_generations=6, n_pop=10, backend="python",
    sa_chains=4,
)


def _pf_record(res) -> tuple:
    return (res.cost, res.solution.state_dict(), res.iterations,
            res.params["barriers"], res.params["migrations"])


@pytest.mark.parametrize("n_shards", [2, 5])
def test_portfolio_n_shards_bit_identical(n_shards):
    prob = _problem(61)
    kw = dict(_PF, n_islands=5, algorithms=("sa-s",), seed=3)
    base = pack_portfolio(prob, **kw)
    shrd = pack_portfolio(prob, n_shards=n_shards, **kw)
    assert _pf_record(shrd) == _pf_record(base)
    assert shrd.params["n_shards"] == n_shards


def test_portfolio_mixed_lineup_n_shards_bit_identical():
    prob = _problem(62)
    kw = dict(_PF, n_islands=4, seed=0)  # ga-nfd + sa-s + sa-nfd + ga-nfd
    base = pack_portfolio(prob, **kw)
    shrd = pack_portfolio(prob, n_shards=2, **kw)
    assert _pf_record(shrd) == _pf_record(base)


def test_portfolio_mesh_bit_identical_and_fuse_needs_one_shard():
    mesh = _mesh_or_skip(2)
    prob = _problem(63)
    kw = dict(
        _KW, max_iterations=128, max_generations=5, n_pop=10, backend="ref",
        sa_chains=4, n_islands=4, algorithms=("sa-s", "ga-nfd"), seed=3,
    )
    base = pack_portfolio(prob, **kw)
    shrd = pack_portfolio(prob, mesh=mesh, **kw)
    assert _pf_record(shrd) == _pf_record(base)
    # one fleet shard keeps fused dispatch on; splitting the fleet turns it
    # off (the fused kernel needs the whole fleet in one block state) while
    # staying bit-identical
    assert shrd.params["fused"] == base.params["fused"]
    split = pack_portfolio(prob, mesh=mesh, n_shards=2, **kw)
    assert _pf_record(split) == _pf_record(base)
    assert split.params["fused"] is False


# ---------------------------------------- resume across shard counts (PR 8)
@pytest.mark.parametrize("save_shards,resume_shards", [(4, 1), (1, 4), (3, 2)])
def test_sweep_resume_across_shard_counts(tmp_path, save_shards, resume_shards):
    probs = [_problem(s) for s in (71, 72, 73, 74, 75)]
    kw = dict(_SA, max_iterations=600)
    base = _record(pack_sweep(probs, "sa-s", seed=3, **kw))
    d = str(tmp_path / "ck")
    with pytest.raises(SimulatedCrash):
        pack_sweep(probs, "sa-s", seed=3, checkpoint_dir=d,
                   checkpoint_every=128, n_shards=save_shards,
                   on_checkpoint=crash_at(2), **kw)
    out = pack_sweep(probs, "sa-s", seed=3, checkpoint_dir=d,
                     checkpoint_every=128, n_shards=resume_shards,
                     resume=True, **kw)
    assert _record(out) == base


@pytest.mark.parametrize("save_shards,resume_shards", [(4, 1), (1, 4)])
def test_portfolio_resume_across_shard_counts(tmp_path, save_shards,
                                              resume_shards):
    prob = _problem(81)
    kw = dict(_PF, max_iterations=512, n_islands=5, algorithms=("sa-s",),
              seed=3)
    base = _pf_record(pack_portfolio(prob, **kw))
    d = str(tmp_path / "ck")
    with pytest.raises(SimulatedCrash):
        pack_portfolio(prob, checkpoint_dir=d, checkpoint_every=2,
                       n_shards=save_shards, on_checkpoint=crash_at(2), **kw)
    out = pack_portfolio(prob, checkpoint_dir=d, checkpoint_every=2,
                         n_shards=resume_shards, resume=True, **kw)
    assert _pf_record(out) == base


def test_sweep_sharded_checkpoint_matches_unsharded_layout(tmp_path):
    """A snapshot cut by a sharded sweep restores into an UNsharded resume
    and vice versa because both use one canonical merged layout — also
    covered above; this pins the single-shard merge == encode equivalence
    used for backward compatibility with PR-6 snapshots."""
    from repro.core.resume import encode_block_state, merge_block_states
    from repro.core.api import make_packer

    probs = [_problem(s) for s in (91, 92, 93)]
    packer = make_packer("sa-s", seed=0, max_seconds=1e9, patience=10**9,
                         max_iterations=64, n_chains=4, backend="python")
    packer._hetero = False
    rngs = [np.random.default_rng(s) for s in (1, 2, 3)]
    st = packer._block_start(probs, rngs, [[], [], []], "python")
    packer._block_run(st, 64)
    a0, e0 = encode_block_state(st)
    a1, e1 = merge_block_states([st])
    assert set(a0) == set(a1)
    for k in a0:
        np.testing.assert_array_equal(a0[k], a1[k])
    assert {k: v for k, v in e0.items() if k not in ("rngs", "traces")} == \
           {k: v for k, v in e1.items() if k not in ("rngs", "traces")}
    assert e0["rngs"] == e1["rngs"] and e0["traces"] == e1["traces"]
