"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.problem import BRAM18_MODES
from repro.kernels.binpack_fitness.kernel import binpack_fitness_pallas
from repro.kernels.binpack_fitness.ops import population_costs
from repro.kernels.binpack_fitness.ref import binpack_fitness_ref
from repro.kernels.binpack_sa_step.ops import metropolis_mask, sa_step_deltas
from repro.kernels.packed_gather.kernel import packed_gather_matvec
from repro.kernels.packed_gather.ops import bank_matvec, split_outputs
from repro.kernels.packed_gather.ref import packed_gather_ref


@pytest.mark.parametrize("p,nb", [(1, 1), (4, 37), (50, 300), (8, 128), (75, 1000)])
def test_binpack_fitness_matches_ref(p, nb, rng):
    w = rng.integers(0, 80, (p, nb)).astype(np.int32)
    w[rng.random((p, nb)) < 0.25] = 0
    h = rng.integers(1, 70_000, (p, nb)).astype(np.int32)
    a = binpack_fitness_pallas(jnp.asarray(w), jnp.asarray(h), BRAM18_MODES, True)
    b = binpack_fitness_ref(jnp.asarray(w), jnp.asarray(h), BRAM18_MODES)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_binpack_fitness_against_core_solution(rng):
    """Kernel totals must equal the core Solution.cost() bookkeeping."""
    import repro.core as c

    prob = c.get_problem("CNV-W2A2")
    sol = c.nfd_from_scratch(prob, np.random.default_rng(0))
    nb = len(sol.bins)
    w = np.zeros((1, nb), np.int32)
    h = np.zeros((1, nb), np.int32)
    for i, b in enumerate(sol.bins):
        bw, bh, _ = prob.bin_stats(b)
        w[0, i], h[0, i] = bw, bh
    total = population_costs(jnp.asarray(w), jnp.asarray(h))
    assert int(total[0]) == sol.cost()


@pytest.mark.parametrize("c,t", [(1, 1), (3, 4), (16, 8), (9, 130), (40, 2)])
def test_sa_step_deltas_backends_agree(c, t, rng):
    """python/ref/pallas SA-step deltas are identical and equal the direct
    per-bin cost difference, with zero-padded (empty) slots contributing 0."""
    ow = rng.integers(0, 80, (c, t)).astype(np.int32)
    ow[rng.random((c, t)) < 0.3] = 0
    oh = np.where(ow > 0, rng.integers(1, 70_000, (c, t)), 0).astype(np.int32)
    nw = rng.integers(0, 80, (c, t)).astype(np.int32)
    nw[rng.random((c, t)) < 0.3] = 0
    nh = np.where(nw > 0, rng.integers(1, 70_000, (c, t)), 0).astype(np.int32)
    py = sa_step_deltas(ow, oh, nw, nh, backend="python")
    rf = sa_step_deltas(ow, oh, nw, nh, backend="ref")
    pa = sa_step_deltas(ow, oh, nw, nh, backend="pallas")
    assert np.array_equal(py, rf)
    assert np.array_equal(py, pa)
    direct = np.asarray(
        binpack_fitness_ref(jnp.asarray(nw), jnp.asarray(nh), BRAM18_MODES)
    ).sum(1) - np.asarray(
        binpack_fitness_ref(jnp.asarray(ow), jnp.asarray(oh), BRAM18_MODES)
    ).sum(1)
    assert np.array_equal(py, direct)


def _random_kind_tables(rng):
    tables = []
    for _ in range(int(rng.integers(1, 4))):
        modes = tuple(
            (int(rng.integers(1, 96)), int(rng.integers(1, 40_000)))
            for _ in range(int(rng.integers(1, 6)))
        )
        tables.append((int(rng.integers(1, 32)), modes))
    return tuple(tables)


@pytest.mark.parametrize("seed", range(12))
def test_random_mode_sets_backends_agree(seed):
    """Seeded random-RAM-mode-set sweep (no hypothesis dependency): the
    numpy, jnp-ref, and Pallas per-kind cost evaluators must all equal the
    scalar min-over-modes formulation for arbitrary mode tables/weights."""
    from repro.kernels.binpack_fitness.kernel import binpack_fitness_kinds_pallas
    from repro.kernels.binpack_fitness.ref import binpack_fitness_kinds_ref
    from repro.kernels.binpack_sa_step.ops import _bin_costs_kinds_numpy

    rng = np.random.default_rng(seed)
    kind_tables = _random_kind_tables(rng)
    p, nb = int(rng.integers(1, 6)), int(rng.integers(1, 150))
    w = rng.integers(0, 100, (p, nb)).astype(np.int32)
    h = np.where(w > 0, rng.integers(1, 60_000, (p, nb)), 0).astype(np.int32)
    k = rng.integers(0, len(kind_tables), (p, nb)).astype(np.int32)
    legacy = np.zeros((p, nb), dtype=np.int64)
    for i in range(p):
        for j in range(nb):
            if w[i, j] > 0:
                weight, modes = kind_tables[int(k[i, j])]
                legacy[i, j] = weight * min(
                    -(-int(w[i, j]) // mw) * -(-int(h[i, j]) // md)
                    for mw, md in modes
                )
    python = _bin_costs_kinds_numpy(w, h, k, kind_tables)
    ref = np.asarray(
        binpack_fitness_kinds_ref(
            jnp.asarray(w), jnp.asarray(h), jnp.asarray(k), kind_tables
        )
    )
    pallas = np.asarray(
        binpack_fitness_kinds_pallas(
            jnp.asarray(w), jnp.asarray(h), jnp.asarray(k), kind_tables, True
        )
    )
    np.testing.assert_array_equal(python, legacy)
    np.testing.assert_array_equal(ref, legacy)
    np.testing.assert_array_equal(pallas, legacy)


@pytest.mark.parametrize("c,t", [(1, 1), (3, 4), (9, 130)])
def test_sa_step_deltas_kinds_backends_agree(c, t, rng):
    """Kind-lane SA deltas: python/ref/pallas agree and equal the direct
    per-kind cost difference (kind flips = same geometry, different kind)."""
    from repro.core.problem import BRAM18, URAM288
    from repro.kernels.binpack_fitness.ref import binpack_fitness_kinds_ref

    kind_tables = ((1, BRAM18.modes), (16, URAM288.modes))
    ow = rng.integers(0, 80, (c, t)).astype(np.int32)
    ow[rng.random((c, t)) < 0.3] = 0
    oh = np.where(ow > 0, rng.integers(1, 70_000, (c, t)), 0).astype(np.int32)
    ok = rng.integers(0, 2, (c, t)).astype(np.int32)
    nw = ow.copy()  # kind flips: geometry fixed, kinds flipped for half
    nh = oh.copy()
    nk = np.where(rng.random((c, t)) < 0.5, 1 - ok, ok).astype(np.int32)
    py = sa_step_deltas(ow, oh, nw, nh, backend="python",
                        old_k=ok, new_k=nk, kind_tables=kind_tables)
    rf = sa_step_deltas(ow, oh, nw, nh, backend="ref",
                        old_k=ok, new_k=nk, kind_tables=kind_tables)
    pa = sa_step_deltas(ow, oh, nw, nh, backend="pallas",
                        old_k=ok, new_k=nk, kind_tables=kind_tables)
    assert np.array_equal(py, rf)
    assert np.array_equal(py, pa)
    direct = np.asarray(
        binpack_fitness_kinds_ref(
            jnp.asarray(nw), jnp.asarray(nh), jnp.asarray(nk), kind_tables
        )
    ).sum(1) - np.asarray(
        binpack_fitness_kinds_ref(
            jnp.asarray(ow), jnp.asarray(oh), jnp.asarray(ok), kind_tables
        )
    ).sum(1)
    assert np.array_equal(py, direct)


# ------------------------------------------- one stacked block per SA step
def _two_kinds():
    from repro.core.problem import BRAM18, URAM288

    return ((1, BRAM18.modes), (16, URAM288.modes))


def _sa_planes(shape, kinds, seed):
    """Random touched-bin planes with zero-width (empty) slots in every row,
    and, with a problem axis, one whole padded (all-empty) problem."""
    rng = np.random.default_rng(seed)
    planes = []
    for _ in range(2):  # old, new
        w = rng.integers(0, 80, shape).astype(np.int32)
        w[rng.random(shape) < 0.3] = 0
        w[..., -1] = 0
        h = np.where(w > 0, rng.integers(1, 70_000, shape), 0).astype(np.int32)
        k = rng.integers(0, 2, shape).astype(np.int32)
        if len(shape) == 3:
            w[-1] = h[-1] = k[-1] = 0
        planes.append((w, h, k))
    (ow, oh, ok), (nw, nh, nk) = planes
    kw = dict(old_k=ok, new_k=nk, kind_tables=_two_kinds()) if kinds else {}
    return (ow, oh, nw, nh), kw


_STACK_SHAPES = [(13, 5), (8, 130), (1, 1), (3, 5, 7), (2, 9, 130)]


@pytest.mark.parametrize("shape", _STACK_SHAPES, ids=str)
@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_sa_step_stacked_block_matches_python(backend, kinds, shape):
    """The jax backends, fed one stacked plane block, equal the host numpy
    path bit for bit: rows off the sublane tile, slots off the lane width,
    empty slots and a padded problem, with and without kind lanes."""
    args, kw = _sa_planes(shape, kinds, seed=len(shape) * 100 + shape[-1])
    py = sa_step_deltas(*args, backend="python", **kw)
    got = sa_step_deltas(*args, backend=backend, **kw)
    assert got.shape == shape[:-1] and got.dtype == np.int64
    np.testing.assert_array_equal(got, py)
    if len(shape) == 3:
        np.testing.assert_array_equal(got[-1], 0)


@pytest.mark.parametrize("ndim", [2, 3])
@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_sa_step_one_put_per_call(backend, kinds, ndim, monkeypatch):
    """One host-to-device put per call on the jax backends: the planes go
    over as one (4 or 6, R, T) int32 block."""
    from repro.kernels.binpack_sa_step import ops

    puts = []
    put = ops._put

    def counting_put(block):
        puts.append((block.shape, block.dtype))
        return put(block)

    monkeypatch.setattr(ops, "_put", counting_put)
    shape = (2, 5, 4) if ndim == 3 else (10, 4)
    args, kw = _sa_planes(shape, kinds, seed=7)
    ops.sa_step_deltas(*args, backend=backend, **kw)
    assert puts == [((6 if kinds else 4, 10, 4), np.dtype(np.int32))]


@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
def test_sa_step_dispatch_calls_named_entries(kinds, monkeypatch):
    """The Pallas entries the dispatcher calls are the jitted functions the
    benchmark's trace reduction finds by name (``KERNEL_OPS``)."""
    from bench.tracing import KERNEL_OPS

    from repro.kernels.binpack_sa_step import kernel

    names = KERNEL_OPS["binpack_sa_step"]
    called = []
    for name in names:
        entry = getattr(kernel, name)

        def spy(block, table, _entry=entry):
            called.append(_entry.__name__)
            assert f"@jit_{_entry.__name__}" in _entry.lower(block, table).as_text()
            return _entry(block, table)

        monkeypatch.setattr(kernel, name, spy)
    args, kw = _sa_planes((9, 4), kinds, seed=3)
    sa_step_deltas(*args, backend="pallas", **kw)
    assert called == [
        "sa_step_deltas_kinds_pallas" if kinds else "sa_step_deltas_pallas"
    ]


def test_metropolis_mask_edge_cases():
    d = np.array([-5.0, 0.0, 2.0, 2.0, 1.0])
    t = np.array([0.0, 1.0, 1e12, 1e-12, 0.0])
    u = np.array([0.99, 0.5, 0.5, 0.5, 0.0])
    # downhill always; d=0 accepts (u < 1); hot accepts; frozen rejects
    np.testing.assert_array_equal(
        metropolis_mask(d, t, u), [True, True, True, False, False]
    )


@pytest.mark.parametrize("seed", range(20))
def test_packed_gather_property(seed):
    # seeded random sweep (no hypothesis dependency for the tier-1 suite)
    rng = np.random.default_rng(seed)
    r = 8 * int(rng.integers(1, 7))
    c = 128 * int(rng.integers(1, 5))
    n = int(rng.integers(1, 7))
    bank = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, n, r), jnp.int32)
    a = packed_gather_matvec(bank, x, seg, interpret=True)
    b = packed_gather_ref(bank, x, seg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_packed_gather_split_outputs(rng):
    r, c, n = 24, 128, 3
    bank = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(n), r // n), jnp.int32)
    y = bank_matvec(bank, x, seg, backend="ref")
    parts = split_outputs(y, seg, n)
    assert sum(p.shape[0] for p in parts) == r


@pytest.mark.parametrize("backend", ["python", "ref", "pallas"])
def test_problem_axis_matches_per_problem_slices(backend, rng):
    """The leading problem axis (NP, ., .) must equal stacking the 2-D calls
    per problem on every backend, for both kernels (DSE fleet contract)."""
    if backend != "python":
        npb, p, nb = 3, 4, 17
        w = rng.integers(0, 80, (npb, p, nb)).astype(np.int32)
        w[rng.random((npb, p, nb)) < 0.3] = 0
        h = np.where(w > 0, rng.integers(1, 60_000, (npb, p, nb)), 0).astype(np.int32)
        t3 = np.asarray(
            population_costs(jnp.asarray(w), jnp.asarray(h), backend=backend)
        )
        assert t3.shape == (npb, p)
        per = np.stack([
            np.asarray(population_costs(jnp.asarray(w[i]), jnp.asarray(h[i]),
                                        backend=backend))
            for i in range(npb)
        ])
        np.testing.assert_array_equal(t3, per)
    npb, cc, t = 3, 5, 4
    ow = rng.integers(0, 80, (npb, cc, t)).astype(np.int32)
    oh = np.where(ow > 0, rng.integers(1, 60_000, (npb, cc, t)), 0).astype(np.int32)
    nw = rng.integers(0, 80, (npb, cc, t)).astype(np.int32)
    nh = np.where(nw > 0, rng.integers(1, 60_000, (npb, cc, t)), 0).astype(np.int32)
    d3 = sa_step_deltas(ow, oh, nw, nh, backend=backend)
    assert d3.shape == (npb, cc)
    per = np.stack([
        sa_step_deltas(ow[i], oh[i], nw[i], nh[i], backend=backend)
        for i in range(npb)
    ])
    np.testing.assert_array_equal(d3, per)
    # kind lanes ride the problem axis too
    from repro.core.problem import BRAM18, URAM288

    kt = ((1, BRAM18.modes), (16, URAM288.modes))
    ok = rng.integers(0, 2, (npb, cc, t)).astype(np.int32)
    nk = rng.integers(0, 2, (npb, cc, t)).astype(np.int32)
    dk3 = sa_step_deltas(ow, oh, nw, nh, backend=backend,
                         old_k=ok, new_k=nk, kind_tables=kt)
    perk = np.stack([
        sa_step_deltas(ow[i], oh[i], nw[i], nh[i], backend=backend,
                       old_k=ok[i], new_k=nk[i], kind_tables=kt)
        for i in range(npb)
    ])
    np.testing.assert_array_equal(dk3, perk)


# ------------------------------------------------------ fused portfolio step
@pytest.mark.parametrize("backend", ["python", "ref", "pallas"])
def test_portfolio_step_matches_separate_dispatches(backend, rng):
    """The fused GA-fitness + SA-delta program is bit-identical to the two
    separate kernel dispatches it replaces, on every backend (the
    core.portfolio fused-barrier contract)."""
    from repro.kernels.binpack_portfolio_step.ops import portfolio_step

    a, p, nb, cc, t = 2, 5, 23, 7, 4
    w = rng.integers(0, 80, (a, p, nb)).astype(np.int32)
    w[rng.random((a, p, nb)) < 0.3] = 0
    h = np.where(w > 0, rng.integers(1, 60_000, (a, p, nb)), 0).astype(np.int32)
    ow = rng.integers(0, 80, (cc, t)).astype(np.int32)
    oh = np.where(ow > 0, rng.integers(1, 60_000, (cc, t)), 0).astype(np.int32)
    nw = rng.integers(0, 80, (cc, t)).astype(np.int32)
    nh = np.where(nw > 0, rng.integers(1, 60_000, (cc, t)), 0).astype(np.int32)
    totals, deltas = portfolio_step(w, h, ow, oh, nw, nh, backend=backend)
    assert totals.shape == (a, p) and totals.dtype == np.float64
    assert deltas.shape == (cc,) and deltas.dtype == np.int64
    np.testing.assert_array_equal(
        totals,
        np.asarray(population_costs(jnp.asarray(w), jnp.asarray(h),
                                    backend="ref")),
    )
    np.testing.assert_array_equal(
        deltas, sa_step_deltas(ow, oh, nw, nh, backend="python")
    )


@pytest.mark.parametrize("backend", ["python", "ref", "pallas"])
def test_portfolio_step_kinds_matches_separate_dispatches(backend, rng):
    from repro.core.problem import BRAM18, URAM288
    from repro.kernels.binpack_portfolio_step.ops import portfolio_step

    kt = ((1, BRAM18.modes), (16, URAM288.modes))
    a, p, nb, cc, t = 2, 4, 19, 6, 3
    w = rng.integers(0, 80, (a, p, nb)).astype(np.int32)
    w[rng.random((a, p, nb)) < 0.3] = 0
    h = np.where(w > 0, rng.integers(1, 60_000, (a, p, nb)), 0).astype(np.int32)
    km = rng.integers(0, 2, (a, p, nb)).astype(np.int32)
    ow = rng.integers(0, 80, (cc, t)).astype(np.int32)
    oh = np.where(ow > 0, rng.integers(1, 60_000, (cc, t)), 0).astype(np.int32)
    ok = rng.integers(0, 2, (cc, t)).astype(np.int32)
    nw = rng.integers(0, 80, (cc, t)).astype(np.int32)
    nh = np.where(nw > 0, rng.integers(1, 60_000, (cc, t)), 0).astype(np.int32)
    nk = rng.integers(0, 2, (cc, t)).astype(np.int32)
    totals, deltas = portfolio_step(
        w, h, ow, oh, nw, nh, backend=backend, kinds=km,
        old_k=ok, new_k=nk, kind_tables=kt,
    )
    np.testing.assert_array_equal(
        totals,
        np.asarray(population_costs(
            jnp.asarray(w), jnp.asarray(h), backend="ref",
            kinds=jnp.asarray(km), kind_tables=kt,
        )),
    )
    np.testing.assert_array_equal(
        deltas,
        sa_step_deltas(ow, oh, nw, nh, backend="python",
                       old_k=ok, new_k=nk, kind_tables=kt),
    )


def test_portfolio_step_rejects_partial_kind_lanes(rng):
    """kinds/old_k/new_k/kind_tables are all-or-none: a portfolio's islands
    share one problem, so half-hetero inputs are a caller bug."""
    from repro.kernels.binpack_portfolio_step.ops import portfolio_step

    z = np.zeros((2, 3), dtype=np.int32)
    with pytest.raises(ValueError, match="together"):
        portfolio_step(z, z, z, z, z, z, backend="python", old_k=z)
