"""Cross-problem batched DSE solver: batch codecs, fleet-vs-standalone
bit parity, sweep dedup/cache, and the sweep report.

The load-bearing contract: every candidate in a `pack_sweep` fleet consumes
its own RNG stream inside the batched engines, so its result is
bit-identical to the standalone `pack(...)` run with the same seed and
budgets — batching buys throughput, never different answers.
"""
import numpy as np
import pytest

import repro.core as c
from repro.core.problem import (
    BRAM18,
    URAM288,
    Buffer,
    OCMInventory,
    PackingProblem,
    batch_group_key,
    decode_problem_batch,
    encode_problem_batch,
)
from repro.core.sa import SimulatedAnnealingPacker


def random_problem(rng, hetero=False):
    n = int(rng.integers(2, 40))
    bufs = [
        Buffer(
            width=int(rng.integers(1, 80)),
            depth=int(rng.integers(1, 40_000)),
            layer=int(rng.integers(0, 5)),
        )
        for _ in range(n)
    ]
    ocm = (
        OCMInventory(
            (BRAM18, URAM288),
            (int(rng.integers(-1, 200)), int(rng.integers(-1, 64))),
            name=f"dev{int(rng.integers(100))}",
        )
        if hetero
        else None
    )
    return PackingProblem(
        bufs,
        max_items=int(rng.integers(1, 6)),
        name=f"rp{n}",
        ocm=ocm,
    )


# ------------------------------------------------------------- batch codecs
@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("hetero", [False, True])
def test_problem_batch_round_trip(seed, hetero):
    """Seeded random fleets (varying n / max_items / inventory counts)
    round-trip through the (NB, max_items) envelope codec exactly."""
    rng = np.random.default_rng(seed)
    probs = [random_problem(rng, hetero=hetero) for _ in range(int(rng.integers(1, 7)))]
    if hetero:
        # counts vary per problem but kinds/mode tables are shared
        assert len({batch_group_key(p) for p in probs}) == 1
    batch = encode_problem_batch(probs)
    assert batch.size == len(probs)
    assert batch.n_max == max(p.n for p in probs)
    back = decode_problem_batch(batch)
    for a, b in zip(probs, back):
        np.testing.assert_array_equal(a.widths, b.widths)
        np.testing.assert_array_equal(a.depths, b.depths)
        np.testing.assert_array_equal(a.layers, b.layers)
        assert a.max_items == b.max_items
        assert a.kind_tables == b.kind_tables
        assert a.kind_counts == b.kind_counts
        assert a.name == b.name
        assert (a.ocm is None) == (b.ocm is None)
        assert a.fingerprint() == b.fingerprint()
        # the decoded problem is solver-equivalent: same costs everywhere
        assert a.bin_cost(36, 1024) == b.bin_cost(36, 1024)


def test_problem_batch_masks_and_tables():
    p1 = c.get_problem("CNV-W1A1")
    p2 = c.get_problem("CNV-W2A2", max_items=3)
    batch = encode_problem_batch([p1, p2])
    assert batch.cap_max == 4
    np.testing.assert_array_equal(batch.n, [p1.n, p2.n])
    assert batch.mask[1, p2.n :].sum() == 0 and batch.mask[1, : p2.n].all()
    assert (batch.widths[1, p2.n :] == 0).all()
    wext, dext, lext = batch.ext_tables()
    assert wext.shape == (2, batch.n_max + 1)
    assert wext[0, -1] == dext[0, -1] == 0 and lext[0, -1] == -1


def test_problem_batch_rejects_mixed_cost_models():
    p1 = c.get_problem("CNV-W1A1")
    h1 = c.get_problem("CNV-W1A1", device="U50")
    assert batch_group_key(p1) != batch_group_key(h1)
    with pytest.raises(ValueError):
        encode_problem_batch([p1, h1])
    with pytest.raises(ValueError):
        encode_problem_batch([])


def test_fingerprint_ignores_names_not_structure():
    rows = c.TABLE1_ROWS["CNV-W1A1"]
    a = PackingProblem(c.buffers_from_shape_rows(rows), name="one")
    b = PackingProblem(c.buffers_from_shape_rows(rows), name="two")
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != PackingProblem(
        c.buffers_from_shape_rows(rows), max_items=3
    ).fingerprint()
    assert a.fingerprint() != c.get_problem("CNV-W1A1", device="U50").fingerprint()


# ------------------------------------------------- fleet-vs-standalone parity
_SA_KW = dict(max_seconds=1e9, patience=10**9, max_iterations=250,
              backend="python")


def _standalone_sa(prob, seed, n_chains=4, **kw):
    merged = {**_SA_KW, **kw}
    return c.pack(prob, "sa-s", seed=seed, n_chains=n_chains, **merged)


def test_sweep_singleton_bit_identical_to_pack():
    """The acceptance pin: a one-candidate sweep IS pack(), bit for bit."""
    prob = c.get_problem("CNV-W1A1")
    sw = c.pack_sweep([prob], "sa-s", seed=7, n_chains=4, **_SA_KW)
    ref = _standalone_sa(prob, 7)
    r = sw.results[0]
    assert r.cost == ref.cost
    assert r.solution.bins == ref.solution.bins
    assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace]
    assert r.iterations == ref.iterations
    assert r.params["seed"] == 7


def test_sweep_fleet_matches_standalone_per_problem():
    """Mixed sizes + max_items in one batch: every candidate reproduces its
    standalone trajectory (per-problem RNG streams)."""
    probs = [
        c.get_problem("CNV-W1A1"),
        c.get_problem("CNV-W2A2", max_items=3),
        c.get_problem("Tincy-YOLO"),
    ]
    seeds = [3, 4, 5]
    sw = c.pack_sweep(probs, "sa-s", seeds=seeds, n_chains=3, **_SA_KW)
    assert sw.n_groups == 1  # one shared cost model -> one batched group
    for r, prob, s in zip(sw.results, probs, seeds):
        ref = _standalone_sa(prob, s, n_chains=3)
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins, prob.name
        assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace]
        r.solution.validate()
        assert r.solution.cost() == r.solution.cost_full() == r.cost


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


_SPLIT_KW = {
    "sa-s": dict(max_seconds=1e9, patience=10**9, backend="python",
                 max_iterations=400, n_chains=4),
    "ga-nfd": dict(max_seconds=1e9, patience=10**9, backend="ref",
                   max_generations=8, n_pop=10),
}


@pytest.mark.parametrize("k", [2, 3, 8])
@pytest.mark.parametrize(
    "algorithm,hetero",
    [("sa-s", False), ("sa-s", True), ("ga-nfd", False), ("ga-nfd", True)],
)
def test_sweep_split_fleet_bit_identical(algorithm, hetero, k):
    """A problem's trajectory does not depend on which fleet it shares:
    solving k contiguous slices of a sweep as separate fleets gives, bit
    for bit, the results of the one fleet.  Cost-model grouping, the
    dedup/cache lanes and PackingService micro-batching all rely on it."""
    probs = [_problem(s, hetero) for s in (11, 12, 13, 14, 15)]
    seeds = [5, 1, 8, 3, 2]
    kw = _SPLIT_KW[algorithm]
    whole = c.pack_sweep(probs, algorithm, seeds=seeds, **kw)
    assert whole.n_groups == 1
    split = []
    for part in np.array_split(np.arange(len(probs)), min(k, len(probs))):
        lo, hi = int(part[0]), int(part[-1]) + 1
        split += _record(
            c.pack_sweep(probs[lo:hi], algorithm, seeds=seeds[lo:hi], **kw)
        )
    assert split == _record(whole)


def test_sweep_hetero_fleet_mixed_devices():
    """ZU7EV and U50 share kind tables but not counts: one group, exact
    per-problem inventory penalties, parity incl. kind lanes."""
    probs = [
        c.get_problem("CNV-W1A1", device="ZU7EV"),
        c.get_problem("CNV-W2A2", device="U50"),
    ]
    sw = c.pack_sweep(probs, "sa-s", seeds=[1, 2], n_chains=3, **_SA_KW)
    assert sw.n_groups == 1
    for r, prob, s in zip(sw.results, probs, [1, 2]):
        ref = _standalone_sa(prob, s, n_chains=3)
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins
        assert list(r.solution.kinds) == list(ref.solution.kinds)
        assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace]


def test_sweep_mixed_cost_models_split_groups():
    probs = [
        c.get_problem("CNV-W1A1"),
        c.get_problem("CNV-W1A1", device="U50"),
        c.get_problem("CNV-W2A2"),
    ]
    sw = c.pack_sweep(probs, "sa-s", seeds=[0, 1, 2], n_chains=3, **_SA_KW)
    assert sw.n_groups == 2  # single-kind group + hetero group
    for r, prob, s in zip(sw.results, probs, [0, 1, 2]):
        ref = _standalone_sa(prob, s, n_chains=3)
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins


def test_sweep_intra_layer_and_freezing_parity():
    """Patience small enough to freeze problems early: frozen problems stop
    consuming RNG exactly where the standalone run stops."""
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    kw = dict(max_seconds=1e9, patience=40, max_iterations=400,
              backend="python")
    sw = c.pack_sweep(probs, "sa-s", seeds=[0, 8], n_chains=3,
                      intra_layer=True, **kw)
    for r, prob, s in zip(sw.results, probs, [0, 8]):
        ref = c.pack(prob, "sa-s", seed=s, n_chains=3, intra_layer=True, **kw)
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins
        assert r.iterations == ref.iterations  # froze at the same step
        r.solution.validate(intra_layer=True)


def test_sweep_ga_lockstep_matches_standalone():
    """The lockstep GA driver stacks all problems' generation fitness into
    one (P, n_pop, NB) kernel call without forking any trajectory."""
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    kw = dict(max_seconds=1e9, patience=10**9, max_generations=10,
              backend="ref")
    sw = c.pack_sweep(probs, "ga-nfd", seeds=[5, 6], **kw)
    assert sw.n_groups == 1
    for r, prob, s in zip(sw.results, probs, [5, 6]):
        ref = c.pack(prob, "ga-nfd", seed=s, **kw)
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins
        assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace]


def test_sweep_serial_fallback_lanes():
    """sa-nfd (scalar-only) and heuristics run the serial lane and still
    match pack() exactly."""
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    for algo, kw in (
        ("sa-nfd", dict(max_seconds=1e9, patience=10**9, max_iterations=60,
                        backend="python")),
        ("nfd", {}),
        ("ffd", {}),
    ):
        sw = c.pack_sweep(probs, algo, seeds=[1, 2], **kw)
        for r, prob, s in zip(sw.results, probs, [1, 2]):
            ref = c.pack(prob, algo, seed=s, **kw)
            assert r.cost == ref.cost, (algo, prob.name)
            assert r.solution.bins == ref.solution.bins


# ----------------------------------------------------------- dedup + caching
def test_sweep_dedup_and_cache():
    prob = c.get_problem("CNV-W1A1")
    clone = PackingProblem(c.get_buffers("CNV-W1A1"), name="renamed-dup")
    other = c.get_problem("CNV-W2A2")
    cache: dict = {}
    sw = c.pack_sweep([prob, clone, other], "sa-s", seed=0, n_chains=3,
                      cache=cache, **_SA_KW)
    # the renamed duplicate is served by fingerprint dedup, not solved
    assert sw.n_solved == 2 and sw.cache_hits == 1
    assert sw.results[0] is sw.results[1]
    assert len(cache) == 2
    # params counters split the dedup/cache sources (PR 8): the duplicate is
    # a dedup hit (same fingerprint in one fleet), not a cache hit
    assert sw.params["solved"] == 2
    assert sw.params["dedup_hits"] == 1
    assert sw.params["cache_hits"] == 0
    # a second sweep over a superset is served entirely from the cache
    sw2 = c.pack_sweep([prob, other, clone], "sa-s", seed=0, n_chains=3,
                       cache=cache, **_SA_KW)
    assert sw2.n_solved == 0 and sw2.cache_hits == 3
    assert sw2.results[0].cost == sw.results[0].cost
    # 2 unique tasks served from the cache, the clone collapsed by dedup
    assert sw2.params["solved"] == 0
    assert sw2.params["cache_hits"] == 2
    assert sw2.params["dedup_hits"] == 1
    assert (sw2.params["solved"] + sw2.params["cache_hits"]
            + sw2.params["dedup_hits"]) == sw2.size
    # different seed or budget = different task = fresh solve
    sw3 = c.pack_sweep([prob], "sa-s", seed=1, n_chains=3, cache=cache,
                       **_SA_KW)
    assert sw3.n_solved == 1


def test_sweep_seed_validation_and_empty():
    prob = c.get_problem("CNV-W1A1")
    with pytest.raises(ValueError):
        c.pack_sweep([], "sa-s")
    with pytest.raises(ValueError):
        c.pack_sweep([prob], "sa-s", seeds=[1, 2])


# ------------------------------------------------------------- sweep report
def test_sweep_report_and_pareto():
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    sw = c.pack_sweep(probs, "nfd", seed=0)
    assert sw.size == 2
    assert sw.candidates_per_sec > 0
    pareto = sw.pareto_indices()
    assert pareto  # the front is never empty
    # every non-front candidate is dominated by some front candidate
    cost, eff = sw.costs(), [r.efficiency for r in sw.results]
    for i in range(sw.size):
        if i not in pareto:
            assert any(
                cost[j] <= cost[i] and eff[j] >= eff[i] for j in pareto
            )
    text = sw.table()
    assert "CNV-W1A1" in text and "pareto" in text and "solve" in text
    assert sw.summary() in text


def test_sweep_equal_budget_costs_match_serial():
    """The ISSUE acceptance criterion's cost half: at equal iteration
    budgets the batched sweep's per-problem costs equal the serial loop's
    (they are the same trajectories)."""
    probs = [
        c.get_problem(name, device=dev)
        for name in ("CNV-W1A1", "CNV-W2A2")
        for dev in (None, "ZU7EV")
    ]
    sw = c.pack_sweep(probs, "sa-s", seed=0, n_chains=3, **_SA_KW)
    serial = [_standalone_sa(p, 0, n_chains=3) for p in probs]
    assert [r.cost for r in sw.results] == [r.cost for r in serial]


def test_sweep_frozen_problem_not_revived_by_exchange():
    """Regression: the fleet exchange tick must skip frozen problems.

    With ``patience < exchange_every`` windows a problem can freeze between
    exchange ticks while a fleet-mate stays live; the exchange used to
    reset the frozen problem's worst chain (``stale = 0``), reviving it to
    draw RNG its standalone run never draws.  Iterations (and thus
    trajectories) must match the standalone runs exactly.
    """
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("RN101-W1A2")]
    kw = dict(max_seconds=1e9, patience=60, max_iterations=20_000,
              exchange_every=70, backend="python")
    sw = c.pack_sweep(probs, "sa-s", seeds=[0, 1], n_chains=3, **kw)
    for r, prob, s in zip(sw.results, probs, [0, 1]):
        ref = c.pack(prob, "sa-s", seed=s, n_chains=3, **kw)
        assert r.iterations == ref.iterations, prob.name
        assert r.cost == ref.cost, prob.name
        assert r.solution.bins == ref.solution.bins, prob.name
        assert [cc for _, cc in r.trace] == [cc for _, cc in ref.trace]


# ------------------------------------------------- block engine direct access
def test_anneal_block_warm_starts():
    """The fleet engine accepts per-problem warm-start chain lists."""
    probs = [c.get_problem("CNV-W1A1"), c.get_problem("CNV-W2A2")]
    packer = SimulatedAnnealingPacker(
        perturbation="swap", backend="python", n_chains=3,
        max_seconds=1e9, patience=10**9, max_iterations=150,
    )
    packer._hetero = False
    rngs = [np.random.default_rng(0), np.random.default_rng(1)]
    first = packer._anneal_block(probs, rngs, [[], []], "python")
    inits = [blk.chains for blk in first]
    rngs = [np.random.default_rng(2), np.random.default_rng(3)]
    second = packer._anneal_block(probs, rngs, inits, "python")
    for blk, prev in zip(second, first):
        blk.best.validate()
        # the run's best never loses to the warm chains it started from
        assert blk.best_cost <= min(s.cost() for s in prev.chains)
