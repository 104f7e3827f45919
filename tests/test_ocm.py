"""Heterogeneous OCM model: RAM kinds, inventories, kind-aware engines.

Golden costs are hand-checked:

* URAM288 is a single 72x4096 aspect: a (72, 4096) bin is exactly 1 URAM;
  (73, 4096) needs 2 (width split); (72, 4097) needs 2 (depth split).
* BRAM36 modes mirror BRAM18 at twice the depth: a (36, 1024) bin is 1
  BRAM36 (vs 2 BRAM18), a (36, 1025) bin is 2.
* On a BRAM18+URAM288 inventory the shared cost unit is 18432 bits, so one
  URAM weighs 16 units and all costs stay exactly comparable.
"""
import random

import numpy as np
import pytest

import repro.core as c
from repro.core.ga import GeneticPacker, buffer_swap, kind_reassign
from repro.core.nfd import nfd_from_scratch, nfd_repack
from repro.core.problem import (
    _GH,
    _GW,
    BRAM18,
    BRAM36,
    LUTRAM64,
    URAM288,
    Buffer,
    OCMInventory,
    PackingProblem,
    RAMKind,
    Solution,
    decode_chain_items,
    encode_chain_items,
    encode_chain_kinds,
    greedy_assign_kinds,
)
from repro.core.sa import SimulatedAnnealingPacker


def hetero_problem(rng, n=30, bram18=10, uram=8, max_items=4):
    bufs = [
        Buffer(
            width=int(rng.integers(1, 80)),
            depth=int(rng.integers(1, 20_000)),
            layer=int(rng.integers(0, 5)),
        )
        for _ in range(n)
    ]
    return PackingProblem(
        bufs,
        ocm=OCMInventory((BRAM18, URAM288), (bram18, uram)),
        max_items=max_items,
    )


# ------------------------------------------------------------- golden costs
def test_uram288_golden_costs():
    prob = PackingProblem(
        [Buffer(1, 1, 0)], ocm=OCMInventory((BRAM18, URAM288), (-1, -1))
    )
    uram = 1  # kind index
    assert prob.bin_primitives(72, 4096, uram) == 1
    assert prob.bin_primitives(73, 4096, uram) == 2
    assert prob.bin_primitives(72, 4097, uram) == 2
    assert prob.bin_primitives(1, 1, uram) == 1
    assert prob.bin_primitives(144, 8192, uram) == 4
    # unit weighting: gcd(18432, 294912) = 18432 -> URAM weighs 16 units
    assert prob.cost_unit_bits == 18432
    assert prob.kind_weights == (1, 16)
    assert prob.bin_cost(72, 4096, uram) == 16
    # BRAM18 lane unchanged vs the homogeneous model
    ref = PackingProblem([Buffer(1, 1, 0)])
    for w, h in [(36, 1024), (1, 16384), (7, 5000), (72, 4096)]:
        assert prob.bin_cost(w, h, 0) == ref.bin_cost(w, h)
    # best_kind: ties resolve to the lowest index (BRAM18's fine-grained
    # modes make it per-unit optimal whenever capacities are commensurate)
    assert prob.best_kind(72, 4096) == 0
    assert prob.best_kind(1, 1) == 0


def test_bram36_golden_costs():
    prob = PackingProblem(
        [Buffer(1, 1, 0)], ocm=OCMInventory((BRAM36,), (-1,))
    )
    assert prob.kind_weights == (1,)
    assert prob.cost_unit_bits == 36 * 1024
    assert prob.bin_cost(36, 1024) == 1
    assert prob.bin_cost(36, 1025) == 2
    assert prob.bin_cost(1, 32768) == 1
    assert prob.bin_cost(72, 512) == 1
    assert prob.bin_cost(2, 16500) == 2  # (2, 16384) mode: ceil(16500/16384)*1
    # joint BRAM18+BRAM36 inventory: BRAM36 weighs 2 BRAM18 units
    joint = PackingProblem(
        [Buffer(1, 1, 0)], ocm=OCMInventory((BRAM18, BRAM36), (-1, -1))
    )
    assert joint.kind_weights == (1, 2)
    assert joint.bin_cost(36, 1024, 1) == 2  # 1 primitive x weight 2


def test_lutram_gcd_unit():
    prob = PackingProblem(
        [Buffer(1, 1, 0)], ocm=OCMInventory((BRAM18, LUTRAM64), (-1, -1))
    )
    assert prob.cost_unit_bits == 64
    assert prob.kind_weights == (288, 1)
    assert prob.bin_cost(1, 64, 1) == 1  # one LUTRAM64 unit
    assert prob.bin_cost(1, 16384, 0) == 288  # one BRAM18 in LUTRAM units
    assert prob.best_kind(1, 64) == 1  # tiny buffer: LUTRAM beats a BRAM18


def test_inventory_validation_and_registry():
    with pytest.raises(ValueError):
        OCMInventory((), ())
    with pytest.raises(ValueError):
        OCMInventory((BRAM18,), (1, 2))
    with pytest.raises(ValueError):
        OCMInventory((BRAM18, BRAM18), (1, 2))
    with pytest.raises(ValueError):
        PackingProblem(
            [Buffer(1, 1, 0)],
            bram=c.BRAMSpec(),
            ocm=OCMInventory((BRAM18,), (-1,)),
        )
    inv = OCMInventory.from_counts("dev", BRAM18=4, URAM288=2)
    assert inv.kind_index("URAM288") == 1
    assert inv.capacity_units() == 4 + 2 * 16
    assert c.RAM_KINDS["URAM288"] is URAM288


def test_device_presets():
    prob = c.get_problem("RN152-W1A2", device="U50")
    assert prob.n_kinds == 2
    assert prob.name == "RN152-W1A2@U50"
    assert prob.kind_counts == (2688, 640)
    # deep ResNet overflows BRAM18 alone but fits the mixed inventory
    assert prob.singleton_solution().inventory_overflow() > 0
    sol = nfd_from_scratch(prob, np.random.default_rng(0))
    assert sol.inventory_overflow() == 0
    assert int(sol.used_primitives()[1]) > 0  # URAM actually used
    with pytest.raises(KeyError):
        c.get_ocm("ZX9000")


# -------------------------------------------------- accounting + invariants
def test_default_problem_is_single_kind():
    prob = c.get_problem("CNV-W1A1")
    assert prob.n_kinds == 1
    assert prob.kind_weights == (1,)
    assert prob.cost_unit_bits == c.BRAM18_CAPACITY_BITS
    sol = prob.singleton_solution()
    assert sol.inventory_overflow() == 0
    assert list(sol.kinds) == [0] * len(sol.bins)


def test_used_primitives_and_overflow():
    prob = PackingProblem(
        [Buffer(36, 1024, 0), Buffer(72, 4096, 1), Buffer(36, 512, 2)],
        ocm=OCMInventory((BRAM18, URAM288), (2, 1)),
        max_items=1,
    )
    sol = Solution(prob, [[0], [1], [2]], kinds=[0, 1, 0])
    np.testing.assert_array_equal(sol.used_primitives(), [3, 1])
    # 3 BRAM18 used vs 2 available -> 1 unit over; URAM within budget
    assert sol.inventory_overflow() == 1
    assert sol.cost() == 2 + 16 + 1
    assert sol.cost() == sol.cost_full()
    sol.set_kind(0, 1)  # move the (36,1024) bin to URAM
    np.testing.assert_array_equal(sol.used_primitives(), [1, 2])
    assert sol.inventory_overflow() == 16  # 2 URAM used vs 1 -> 16 units over
    assert sol.cost() == 16 + 16 + 1 == sol.cost_full()


@pytest.mark.parametrize("seed", range(6))
def test_incremental_cost_matches_full_hetero(seed):
    """Kind-aware geometry cache vs from-scratch rescan under chains of all
    three mutation operators (repack, swap with kind moves, reassign)."""
    rng = np.random.default_rng(seed)
    prob = hetero_problem(rng, n=int(rng.integers(5, 40)))
    sol = nfd_from_scratch(prob, rng, p_adm_h=0.2)
    for step in range(12):
        if step % 3 == 0:
            sol = nfd_repack(sol, rng, threshold=0.9, extra_frac=0.1, p_adm_h=0.3)
        elif step % 3 == 1:
            sol = buffer_swap(sol, rng, n_moves=3, p_kind=0.5)
        else:
            sol = kind_reassign(sol, rng, n_moves=2)
        sol.validate()
        assert sol.cost() == sol.cost_full()
        np.testing.assert_allclose(
            sol.bin_efficiencies(), sol.bin_efficiencies_full()
        )


def test_greedy_assign_kinds_relieves_overflow():
    rng = np.random.default_rng(1)
    # 20 bins of 8 BRAM18 each = 160 primitives on 40 available: must offload
    bufs = [Buffer(32, 4096, i % 3) for i in range(20)]
    prob = PackingProblem(
        bufs, ocm=OCMInventory((BRAM18, URAM288), (40, 64)), max_items=1
    )
    sol = prob.singleton_solution()
    assert sol.inventory_overflow() > 0
    greedy_assign_kinds(sol)
    sol.validate()
    assert sol.inventory_overflow() == 0
    assert sol.cost() == sol.cost_full()


def _rescan_assign_kinds(sol: Solution) -> Solution:
    """Test oracle: the original greedy kind assignment, which rescans
    every bin for every move."""
    p = sol.problem
    if p.n_kinds == 1 or not p._any_bounded:
        return sol
    sol._refresh()
    nb = len(sol.bins)
    nk = p.n_kinds
    g = sol._geom
    wc = np.empty((nb, nk), dtype=np.int64)
    prim = np.empty((nb, nk), dtype=np.int64)
    for bi in range(nb):
        w, h = int(g[bi, _GW]), int(g[bi, _GH])
        for k in range(nk):
            c = p._cost_mode_gap(w, h, k)
            wc[bi, k] = c[0]
            prim[bi, k] = c[3]
    kinds = np.argmin(wc, axis=1).astype(np.int64)
    counts = p._kind_counts_arr
    used = np.zeros(nk, dtype=np.int64)
    ar = np.arange(nb)
    np.add.at(used, kinds, prim[ar, kinds])
    for _ in range(nb + 1):
        over = (counts >= 0) & (used > counts)
        if not over.any():
            break
        cur_wc = wc[ar, kinds]
        cur_prim = prim[ar, kinds]
        movable = over[kinds] & (cur_prim > 0)
        best = None  # (regret per freed primitive, bin, target kind)
        for j in range(nk):
            cand = movable & (kinds != j)
            if counts[j] >= 0:
                cand &= used[j] + prim[:, j] <= counts[j]
            if not cand.any():
                continue
            regret = np.where(cand, (wc[:, j] - cur_wc) / cur_prim, np.inf)
            bi = int(np.argmin(regret))
            if best is None or regret[bi] < best[0]:
                best = (float(regret[bi]), bi, j)
        if best is None:
            break
        _, bi, j = best
        used[kinds[bi]] -= prim[bi, kinds[bi]]
        kinds[bi] = j
        used[j] += prim[bi, j]
    changed = np.flatnonzero(kinds != sol.kinds)
    if changed.size:
        sol.kinds[:] = kinds
        sol.touch(*[int(b) for b in changed])
    return sol


def _nfd_seeds(prob, n, seed=0):
    """NFD packings with their kind lanes reset to kind 0."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        s = nfd_from_scratch(prob, rng)
        s.kinds[:] = 0
        s.invalidate()
        out.append(s)
    return out


def _singletons(specs, kinds, counts):
    """One bin per (width, depth, how many) group, in order."""
    bufs = [Buffer(w, d, 0) for w, d, m in specs for _ in range(m)]
    prob = PackingProblem(bufs, ocm=OCMInventory(kinds, counts), max_items=1)
    return prob.singleton_solution()


def _random_three_kind(n_problems):
    """LUTRAM64/BRAM18/URAM288 problems, the kinds in a random order (ties
    in unit cost go to the first, so the order decides which start over)."""
    rng = np.random.default_rng(7)
    out = []
    for t in range(n_problems):
        bufs = [
            Buffer(int(rng.choice([1, 2, 4, 8, 16, 18, 36, 72, 144])),
                   int(rng.choice([8, 16, 64, 500, 1024, 2048, 4096, 9000])),
                   int(rng.integers(0, 4)))
            for _ in range(int(rng.integers(5, 60)))
        ]
        kinds = tuple(rng.permutation([LUTRAM64, BRAM18, URAM288]))
        counts = tuple(int(x) for x in rng.integers(-1, 40, size=3))
        prob = PackingProblem(
            bufs, ocm=OCMInventory(kinds, counts), max_items=int(rng.integers(1, 5)),
        )
        out += _nfd_seeds(prob, 1, seed=t)
    return out


def _two_over_relief():
    """BRAM18 and LUTRAM64 both start over.  A (18, 2048) bin is 2 BRAM18
    and moves to URAM288 first (regret 2016 per primitive, against 4607 for
    a (4, 16) LUTRAM bin); that leaves one BRAM18 free, which a LUTRAM bin
    (regret 287) then takes."""
    return [_singletons([(4, 16, 6), (18, 2048, 4)],
                        (BRAM18, LUTRAM64, URAM288), (7, 2, 6))]


def _regret_ties():
    """Equal regrets across bins and across target kinds: a (36, 1024) bin
    costs 2 units on BRAM18, on BRAM36 and on a BRAM18 twin alike."""
    twin = RAMKind("BRAM18_TWIN", BRAM18.modes, BRAM18.capacity_bits)
    specs = [(36, 1024, 5), (18, 1024, 4), (36, 2048, 3), (1, 100, 2)]
    return [_singletons(specs, (BRAM18, BRAM36, twin), (6, 3, 5)),
            _singletons(specs, (BRAM18, twin, BRAM36), (4, 4, -1))]


KIND_CASES = {
    "rn152-u50": lambda: _nfd_seeds(c.get_problem("RN152-W1A2", device="U50"), 3),
    "table1-zu7ev": lambda: [
        s for name in ("CNV-W2A2", "Tincy-YOLO", "RN50-W1A2", "RN101-W1A2")
        for s in _nfd_seeds(c.get_problem(name, device="ZU7EV"), 2)
    ],
    "random-3-kind": lambda: _random_three_kind(120),
    "two-over-relief": _two_over_relief,
    "regret-ties": _regret_ties,
    "unbounded-target": lambda: [
        _singletons([(32, 4096, 20)], (BRAM18, URAM288), (40, -1)),
        hetero_problem(np.random.default_rng(3)).singleton_solution(),
    ],
    "infeasible": lambda: [
        _singletons([(32, 4096, 20), (72, 4096, 3)], (BRAM18, URAM288), (10, 2)),
    ],
    "single-kind": lambda: [
        c.get_problem("CNV-W1A1").singleton_solution(),
        _singletons([(32, 4096, 5)], (BRAM18, URAM288), (-1, -1)),
    ],
}


@pytest.mark.parametrize("case", list(KIND_CASES))
def test_greedy_assign_kinds_matches_rescan(case):
    """The sorted walk gives the original rescan's kind lane, cost and
    residual overflow, touches only the changed bins and draws no RNG."""
    for sol in KIND_CASES[case]():
        want = _rescan_assign_kinds(sol.copy())
        before = sol.kinds.copy()
        np_state, py_state = np.random.get_state(), random.getstate()
        got = greedy_assign_kinds(sol)
        assert got is sol
        assert random.getstate() == py_state
        after = np.random.get_state()
        assert np.array_equal(after[1], np_state[1])
        assert after[:1] + after[2:] == np_state[:1] + np_state[2:]
        np.testing.assert_array_equal(sol.kinds, want.kinds)
        assert sol.cost() == want.cost() == sol.cost_full()
        assert sol.inventory_overflow() == want.inventory_overflow()
        if case == "single-kind":
            np.testing.assert_array_equal(sol.kinds, before)
        if case == "infeasible":
            assert sol.inventory_overflow() > 0
        if case == "two-over-relief":  # a LUTRAM bin lands on the relieved BRAM18
            assert list(sol.kinds[:6]).count(0) == 1


def test_chain_codecs_round_trip_kinds():
    rng = np.random.default_rng(2)
    prob = hetero_problem(rng, n=12)
    sols = [nfd_from_scratch(prob, rng) for _ in range(3)]
    for s in sols:
        s.kinds[: len(s.bins) // 2] = 1
        s.invalidate()
    items, counts = encode_chain_items(sols, prob.max_items)
    kinds = encode_chain_kinds(sols, items.shape[1])
    for i, s in enumerate(sols):
        back = decode_chain_items(prob, items[i], counts[i], kinds[i])
        assert back.bins == s.bins
        assert list(back.kinds) == list(s.kinds)
        assert back.cost() == s.cost()


# ---------------------------------------------------------- engine behavior
def _tight_problem():
    bufs = [Buffer(36, 4096, i % 4) for i in range(40)]
    return PackingProblem(
        bufs, ocm=OCMInventory((BRAM18, URAM288), (40, 64)), max_items=4
    )


@pytest.mark.parametrize("algo", ["ga-nfd", "ga-s", "sa-s", "sa-nfd"])
def test_engines_reach_feasibility(algo):
    prob = _tight_problem()
    r = c.pack(prob, algo, seed=0, max_seconds=1.5, backend="python")
    r.solution.validate()
    assert r.solution.cost() == r.solution.cost_full() == r.cost
    assert r.solution.inventory_overflow() == 0
    assert r.params["overflow"] == 0


def test_ga_backends_bit_identical_hetero():
    rng = np.random.default_rng(3)
    prob = hetero_problem(rng, n=25)
    results = {
        backend: GeneticPacker(
            backend=backend, seed=7, max_generations=15,
            max_seconds=1e9, patience=10**9,
        ).pack(prob)
        for backend in ("python", "ref", "pallas")
    }
    ref = results["python"]
    for backend, r in results.items():
        assert r.cost == ref.cost, backend
        assert r.solution.bins == ref.solution.bins, backend
        assert list(r.solution.kinds) == list(ref.solution.kinds), backend
        r.solution.validate()
        assert r.solution.cost() == r.solution.cost_full() == r.cost


def _sa(backend, prob, n_chains=1, **kw):
    kw.setdefault("seed", 5)
    kw.setdefault("max_iterations", 500)
    return SimulatedAnnealingPacker(
        perturbation="swap", backend=backend, n_chains=n_chains,
        max_seconds=1e9, patience=10**9, **kw,
    ).pack(prob)


def test_sa_single_chain_hetero_parity():
    """The scalar loop and the delta engine share the hetero RNG stream and
    exact penalty bookkeeping: identical trajectories on every backend."""
    rng = np.random.default_rng(4)
    prob = hetero_problem(rng, n=30)
    results = {b: _sa(b, prob) for b in ("legacy", "python", "ref", "pallas")}
    ref = results["legacy"]
    for backend, r in results.items():
        assert r.cost == ref.cost, backend
        assert r.solution.bins == ref.solution.bins, backend
        assert list(r.solution.kinds) == list(ref.solution.kinds), backend
        assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace], backend


def test_sa_multi_chain_hetero_backends_identical():
    rng = np.random.default_rng(5)
    prob = hetero_problem(rng, n=25)
    results = [
        _sa(b, prob, n_chains=4, seed=3, max_iterations=300, exchange_every=64)
        for b in ("python", "ref", "pallas")
    ]
    first = results[0]
    for r in results[1:]:
        assert r.cost == first.cost
        assert r.solution.bins == first.solution.bins
        assert list(r.solution.kinds) == list(first.solution.kinds)
    first.solution.validate()
    assert first.solution.cost() == first.solution.cost_full() == first.cost


def test_portfolio_hetero():
    # iteration budgets, not wall-clock: machine-independent, and no
    # TruncationWarning (promoted to an error by pytest.ini) can leak
    prob = _tight_problem()
    r = c.pack_portfolio(
        prob, n_islands=3, seed=0, max_seconds=60.0, backend="python",
        sa_chains=3, max_iterations=1500, max_generations=30,
    )
    r.solution.validate()
    assert r.solution.cost() == r.solution.cost_full() == r.cost
    assert r.cost <= prob.lower_bound() * 40  # sanity: bounded


@pytest.mark.parametrize("backend", ["ref", "pallas", "legacy"])
def test_single_kind_custom_primitive_batched_backends(backend):
    """Regression: batched GA/SA backends must evaluate a single-kind
    problem on ITS mode table, not the hardcoded BRAM18 one (a BRAM36-only
    problem used to get silently wrong costs on ref/pallas)."""
    rng = np.random.default_rng(8)
    bufs = [
        Buffer(int(rng.integers(1, 70)), int(rng.integers(1, 30_000)), int(i % 4))
        for i in range(25)
    ]
    prob = PackingProblem(bufs, ocm=OCMInventory((BRAM36,), (-1,)))
    ref = GeneticPacker(backend="python", seed=7, max_generations=12,
                        max_seconds=1e9, patience=10**9).pack(prob)
    r = GeneticPacker(backend=backend, seed=7, max_generations=12,
                      max_seconds=1e9, patience=10**9).pack(prob)
    assert r.cost == ref.cost
    assert r.solution.bins == ref.solution.bins
    assert r.solution.cost() == r.solution.cost_full() == r.cost
    sa_ref = _sa("legacy", prob, seed=9, max_iterations=300)
    sa_r = _sa(backend if backend != "legacy" else "python", prob,
               seed=9, max_iterations=300)
    assert sa_r.cost == sa_ref.cost
    assert sa_r.solution.bins == sa_ref.solution.bins


def test_default_path_rng_untouched_by_kind_params():
    """p_kind only fires on heterogeneous problems: a single-kind run with
    any p_kind matches the stock trajectory exactly."""
    prob = c.get_problem("CNV-W1A1")
    a = GeneticPacker(seed=11, max_generations=10, backend="python",
                      max_seconds=1e9, patience=10**9).pack(prob)
    b = GeneticPacker(seed=11, max_generations=10, backend="python",
                      max_seconds=1e9, patience=10**9, p_kind=0.9).pack(prob)
    assert a.cost == b.cost
    assert a.solution.bins == b.solution.bins
