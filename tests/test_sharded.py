"""Mesh-sharded fleet execution (PR 8): ``mesh=`` parity.

``mesh=`` makes the batched kernels row-shard every step over a 1-D
``("prob",)`` device mesh via ``shard_map`` (exact integer arithmetic, so
bit-identical by construction).  Kernel- and engine-level mesh parity
tests need >= 2 devices and skip otherwise; the CI sharded-smoke lane runs
this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
That a problem's trajectory does not depend on which fleet it shares is
pinned in ``tests/test_dse.py`` (``test_sweep_split_fleet_bit_identical``).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import pack_portfolio, pack_sweep

from test_dse import _problem, _record


def _n_devices() -> int:
    import jax

    return len(jax.devices())


def _mesh_or_skip(k: int):
    if _n_devices() < k:
        pytest.skip(f"needs {k} devices (CI sharded lane forces 8)")
    from repro.launch.mesh import make_sweep_mesh

    return make_sweep_mesh(k)


_KW = dict(max_seconds=1e9, patience=10**9)
_GA = dict(_KW, backend="ref", max_generations=8, n_pop=10)


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


# ------------------------------------------------------- sweep mesh parity
def test_sweep_sa_mesh_bit_identical():
    mesh = _mesh_or_skip(2)
    probs = [_problem(s) for s in (41, 42, 43)]
    kw = dict(_KW, backend="ref", max_iterations=100, n_chains=3)
    base = pack_sweep(probs, "sa-s", seed=5, **kw)
    shrd = pack_sweep(probs, "sa-s", seed=5, mesh=mesh, **kw)
    assert _record(shrd) == _record(base)


def test_sweep_ga_mesh_bit_identical():
    mesh = _mesh_or_skip(2)
    probs = [_problem(s) for s in (51, 52, 53)]
    kw = dict(_GA, max_generations=6)
    base = pack_sweep(probs, "ga-nfd", seed=4, **kw)
    shrd = pack_sweep(probs, "ga-nfd", seed=4, mesh=mesh, **kw)
    assert _record(shrd) == _record(base)


# --------------------------------------------------------- portfolio parity
def _pf_record(res) -> tuple:
    return (res.cost, res.solution.state_dict(), res.iterations,
            res.params["barriers"], res.params["migrations"])


def test_portfolio_mesh_bit_identical():
    mesh = _mesh_or_skip(2)
    prob = _problem(63)
    kw = dict(
        _KW, max_iterations=128, max_generations=5, n_pop=10, backend="ref",
        sa_chains=4, n_islands=4, algorithms=("sa-s", "ga-nfd"), seed=3,
    )
    base = pack_portfolio(prob, **kw)
    shrd = pack_portfolio(prob, mesh=mesh, **kw)
    assert _pf_record(shrd) == _pf_record(base)
    assert shrd.params["fused"] == base.params["fused"]
