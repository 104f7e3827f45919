"""``nfd_repack`` against a frozen copy of its earlier, whole-packing form.

The repack builds the child from the selected bins alone; the earlier form
walked every bin of the parent twice.  Both must give the same child (bins,
kind lane, geometry rows, dirty mask) and leave the generator in the same
state, so every GA-NFD and SA-NFD trajectory is unchanged; at the benchmark
cell's settings GA-NFD still matches the plain reference's replay."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import repro.core as c
from bench import harness
from bench.reference import audit, canonical, replay_ga_nfd
from repro.core.nfd import (
    nfd_from_scratch, nfd_pack_order, nfd_repack, select_repack_bins,
)
from repro.core.problem import Solution

CONFIG = Path(__file__).resolve().parents[1] / "bench/configs/rn152-w1a2-bram18.json"
STEPS = 12


def frozen_nfd_repack(sol, rng, threshold=0.95, p_adm_w=0.0, p_adm_h=0.1,
                      intra_layer=False, extra_frac=0.0, max_bins=12, use_cache=True):
    prob = sol.problem
    mask = select_repack_bins(
        sol, rng, threshold, max_bins, extra_frac, use_cache=use_cache
    )
    keep = [b for b, m in zip(sol.bins, mask) if not m]
    pool = np.asarray(
        [i for b, m in zip(sol.bins, mask) if m for i in b], dtype=np.int64
    )
    rng.shuffle(pool)
    if intra_layer:
        pool = pool[np.argsort(prob.layers[pool], kind="stable")]
    new_bins = nfd_pack_order(
        prob, pool, rng, p_adm_w=p_adm_w, p_adm_h=p_adm_h, intra_layer=intra_layer
    )
    if not use_cache:
        if prob.n_kinds == 1:
            return Solution(prob, keep + new_bins)
        kept_kinds = [int(k) for k, m in zip(sol.kinds, mask) if not m]
        return Solution(
            prob, keep + new_bins, kinds=kept_kinds + [0] * len(new_bins)
        )
    nk, nn = len(keep), len(new_bins)
    geom = np.empty((nk + nn, 6), dtype=np.int64)
    geom[:nk] = sol._geom[~mask]
    dirty = np.empty(nk + nn, dtype=bool)
    dirty[:nk] = sol._dirty[~mask]
    dirty[nk:] = True
    kinds = np.zeros(nk + nn, dtype=np.int64)
    kinds[:nk] = sol.kinds[~mask]
    return Solution._with_geometry(prob, keep + new_bins, geom, dirty, kinds)


@pytest.fixture(scope="module")
def problems():
    return {
        "rn152-bram18": c.get_problem("RN152-W1A2"),
        "rn152-u50": c.get_problem("RN152-W1A2", device="U50"),
    }


def _rng_pair(seed):
    a = np.random.default_rng(seed)
    b = np.random.default_rng()
    b.bit_generator.state = a.bit_generator.state
    return a, b


@pytest.mark.parametrize("use_cache", [True, False])
@pytest.mark.parametrize("extra_frac", [0.0, 0.01])
@pytest.mark.parametrize("intra_layer", [False, True])
@pytest.mark.parametrize("name", ["rn152-bram18", "rn152-u50"])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_repack_matches_the_frozen_form(problems, name, seed, intra_layer,
                                        extra_frac, use_cache):
    prob = problems[name]
    kw = dict(threshold=0.95, p_adm_w=0.0, p_adm_h=0.1, intra_layer=intra_layer,
              extra_frac=extra_frac, max_bins=12, use_cache=use_cache)
    rng_old, rng_new = _rng_pair(seed)
    sol = nfd_from_scratch(prob, np.random.default_rng(seed), intra_layer=intra_layer)
    if not use_cache:
        sol = Solution(prob, sol.bins, kinds=sol.kinds)
    if name == "rn152-u50":
        assert len(set(sol.kinds.tolist())) > 1  # the kept kind lane is exercised
    for _ in range(STEPS):
        old = frozen_nfd_repack(sol, rng_old, **kw)
        new = nfd_repack(sol, rng_new, **kw)
        assert rng_new.bit_generator.state == rng_old.bit_generator.state
        assert new.bins == old.bins
        assert new.kinds.dtype == old.kinds.dtype
        np.testing.assert_array_equal(new.kinds, old.kinds)
        np.testing.assert_array_equal(new._dirty, old._dirty)
        assert new._any_dirty == old._any_dirty
        clean = ~old._dirty
        np.testing.assert_array_equal(new._geom[clean], old._geom[clean])
        assert new.cost() == old.cost() == new.cost_full()
        np.testing.assert_array_equal(new._geom, old._geom)
        sol = new
    sol.validate(intra_layer=intra_layer)


@pytest.mark.parametrize("backend", ["python", "ref"])
def test_ga_nfd_at_the_cell_settings_matches_the_reference(backend):
    """Table 3's problem (RN152-W1A2 on BRAM18 alone) at the benchmark
    configuration's GA-NFD settings, 75 individuals, against the plain
    reference's replay: the same packing, cost and improvement trace."""
    cfg = json.loads(CONFIG.read_text())
    hyper = {k: v for k, v in cfg["solver"].items()
             if k not in ("algorithm", "n_pop", "patience", "max_seconds")}
    n_pop, gens, seed = int(cfg["solver"]["n_pop"]), 10, 2**31 + 5
    prob, ref = harness.program_problems(cfg)[0], harness.reference_problems(cfg)[0]
    res = c.pack(prob, "ga-nfd", seed=seed, n_pop=n_pop, max_generations=gens,
                 backend=backend, max_seconds=1e12, patience=10**12, **hyper)
    want = replay_ga_nfd(ref, seed, n_pop, gens, **hyper)
    assert res.params["backend"] == backend
    assert int(res.cost) == want["cost"]
    assert [x for _, x in res.trace] == want["trace"] and len(want["trace"]) > 2
    assert res.iterations == want["iterations"] == gens
    assert canonical(res.solution.bins, res.solution.kinds) == canonical(
        want["bins"], want["kinds"])
    assert audit(ref, want["bins"], want["kinds"], want["cost"], want["trace"][-1],
                 want["iterations"], gens) == []
