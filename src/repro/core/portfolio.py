"""Multi-seed island portfolio: a deterministic fleet of GA/SA islands.

The paper's hybrid mappers are stochastic — different seeds land on
different local optima.  A *portfolio* run hedges that variance: K islands
(differently-seeded GA/SA instances, possibly with different algorithms or
hyperparameters) evolve on one problem and periodically exchange their best
packing, so good building blocks spread without collapsing diversity.

The portfolio is **fleet-native and iteration-budgeted** — an array
program, not a thread pool:

* Every multi-chain ``sa-s`` island rides the SA fleet core
  (`SimulatedAnnealingPacker._anneal_block`): K same-problem islands are a
  ``P = K`` fleet with problem-major rows and one ``np.random.Generator``
  stream per island, exactly the layout ``core.dse.pack_sweep`` uses for
  cross-problem sweeps — here the "problems" are replicas of one problem.
* GA islands advance generation-by-generation through the `_GARun` phase
  helpers (`ga.lockstep_generation`), stacking every island's population
  fitness into one leading-axis ``(K, n_pop, NB)`` kernel call.
* Scalar engines (``sa-nfd``'s sequential NFD repack, single-chain
  ``sa-s``, ``legacy`` backends) run their own resumable loops — the same
  code path their standalone ``pack()`` uses, advanced in segments.
* **Migration is a deterministic array exchange at fixed barriers**: every
  ``migration_every`` iterations (SA steps) / generations (GA), the global
  best solution is broadcast into each *other* island's worst warm slot
  (worst chain / worst individual / the incumbent), iff strictly better
  under the inventory-penalized cost.  Migration never touches patience
  counters, so it can never revive a frozen island — a frozen island stops
  drawing RNG exactly where its standalone run would.

Because islands advance by iteration counts and each consumes only its own
seeded RNG stream, ``pack_portfolio(prob, seed=s, ...)`` is **bit-
reproducible** run-to-run and machine-independent (given iteration budgets;
``max_seconds`` remains as an outer safety cap only), and a single-island
portfolio is bit-identical to the corresponding standalone ``pack()`` run —
both pinned in ``tests/test_portfolio.py``.  Barrier semantics and the
seed/stream layout: docs/DESIGN.md section 11; the concurrent scheduler,
per-family strides, and fused dispatch: section 13 (parity pins in
``tests/test_portfolio_concurrent.py``).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np

from repro.kernels.platform import pallas_interpret

from .ga import (
    GeneticPacker,
    lockstep_apply,
    lockstep_begin,
    lockstep_finish,
    lockstep_generation,
    stack_geometry,
    stacked_population_costs,
)
from .problem import (
    DEFAULT_INVENTORY_PENALTY,
    PackingProblem,
    PackingResult,
    Solution,
    decode_chain_items,
)
from .sa import SimulatedAnnealingPacker

# default barrier spacing: SA iterations / GA generations between migrations
DEFAULT_MIGRATION_EVERY = 64

# Per-engine-family barrier strides on heterogeneous lineups (>1 engine
# group): one barrier advances the delta-kernel SA engines (fleet and
# single-chain sa-s) ``migration_every`` annealing steps — scaled up by the
# number of GA islands in the lineup, see below — the scalar loops (sa-nfd's
# sequential repack, the legacy backend) a quarter of that base, and the GA
# lockstep pack 1/32 of it in generations.  The divisors are static
# constants — strides depend only on the lineup and ``migration_every``,
# never on machine speed — so trajectories stay bit-reproducible; they exist
# because one GA generation (n_pop mutation repacks + a stacked fitness
# call) costs on the order of `_GA_STRIDE_DIV` vectorized fleet steps *per
# GA island*, and a uniform stride would park the whole barrier on the
# slowest family (the ISSUE-7 "mixed lineup 0.24x threads" pathology).  The
# GA-island multiplier lets the vectorized engines absorb the barrier slack
# instead of idling while a stacked generation finishes.  Homogeneous
# lineups (a single engine group) keep the uniform stride: nothing to
# rebalance, and the fleet path stays exactly PR 5's.
_SCALAR_STRIDE_DIV = 4
_GA_STRIDE_DIV = 32

# Racing ledger currency (``pack_portfolio(auto=True)``): one unit is one
# chain-annealing step.  A fleet island burns ``stride * n_chains`` units per
# barrier, a scalar/single-chain island ``stride``, and a GA island
# ``stride * n_pop * _GA_GEN_WORK`` — one generation mutates and re-evaluates
# on the order of ``n_pop`` individuals, and the stride design above prices a
# default generation (n_pop=50) at ``_GA_STRIDE_DIV`` fleet steps of
# ``sa_chains=8`` chains, i.e. 32*8/50 ~ 5 chain-steps per individual.  The
# weights are static functions of the lineup, so the ledger — and with it
# every elimination decision — is machine-independent.
_GA_GEN_WORK = 5

# Default race grid for ``pack_portfolio(auto=True)``: the hyperparameter
# axes the paper shows the mappers are sensitive to — GA population size and
# mutation rate (Fig. 4/5, reproduced in ``benchmarks/bench_fig45.py``), SA
# chain counts, temperature ladders, and move widths (Table 2 neighborhood).
# Entries are ``(algorithm, hyper-overrides)``; island k races with seed
# ``seed + k``.
DEFAULT_RACE_GRID = (
    ("sa-s", {}),
    ("sa-s", {"n_chains": 16, "ladder_max": 8.0}),
    ("sa-s", {"n_chains": 4, "ladder_min": 0.25, "ladder_max": 1.0}),
    ("sa-s", {"sa_t0": 60.0, "sa_rc": 0.5}),
    ("sa-s", {"sa_t0": 10.0, "sa_rc": 2.0}),
    ("sa-s", {"swap_moves": 4}),
    ("ga-nfd", {}),
    ("ga-nfd", {"n_pop": 25, "p_mut": 0.6}),
    ("ga-nfd", {"n_pop": 150}),
    ("ga-nfd", {"n_pop": 5, "p_mut": 0.8}),
    ("ga-s", {"n_pop": 25}),
    ("sa-nfd", {}),
)

# offset between per-round reseeds of the legacy thread-pool portfolio; any
# large odd constant keeps island streams disjoint from the base seeds
_ROUND_SEED_STRIDE = 7919


class TruncationWarning(RuntimeWarning):
    """A wall-clock cap cut a run short of its iteration/patience budgets —
    the result is NOT seed-reproducible across machines.  Promoted to an
    error in the test suite (``pytest.ini``); tests that intentionally
    exercise the truncation path catch it with ``pytest.warns``."""


@dataclasses.dataclass(frozen=True)
class IslandSpec:
    """One island: which packer, which base seed, which overrides."""

    algorithm: str = "ga-nfd"
    seed: int = 0
    hyper: dict = dataclasses.field(default_factory=dict)


# --------------------------------------------------------------- island views
class _SAFleetGroup:
    """K same-problem sa-s islands advanced as ONE `_anneal_block` fleet.

    Row ``j * C + c`` is chain ``c`` of island ``j``; the bin-slot envelope
    is widened to ``prob.n`` so any migrant packing can be encoded into a
    chain slot (envelope padding never affects trajectories — DESIGN.md
    section 10).

    ``mesh`` row-shards each fleet step over a ``("prob",)`` device mesh —
    an execution-shape knob only: each island consumes only its own RNG
    stream, so the result is bit-identical to the unsharded fleet
    (docs/DESIGN.md section 14, pinned in ``tests/test_sharded.py``)."""

    def __init__(self, packer, prob, rngs, backend, mesh=None):
        self.packer = packer
        self.st = packer._block_start(
            [prob] * len(rngs), list(rngs), [[] for _ in rngs],
            backend, n_slots=prob.n, mesh=mesh,
        )

    def advance(self, limit: int | None) -> bool:
        if self.st.done:
            return False
        before = self.st.it
        self.packer._block_run(self.st, limit)
        return self.st.it > before


class _FleetIsland:
    """View of one member problem of a `_SAFleetGroup`."""

    def __init__(self, group: _SAFleetGroup, j: int):
        self.group = group
        self.j = j
        self.packer = group.packer
        self.eliminated = False

    def done(self) -> bool:
        st, j = self.group.st, self.j
        return st.done or self.packer._block_frozen(st, j)

    def extend(self, it_limit: int) -> None:
        self.packer._block_extend(self.group.st, it_limit)

    def eliminate(self) -> None:
        st, j = self.group.st, self.j
        self.packer._block_eliminate(st, j)
        self.eliminated = True

    def raw(self) -> tuple[int, int]:
        st, j = self.group.st, self.j
        cost = int(st.gbest_cost[j])
        if st.hetero:
            ovf = int(st.batch.overflow_rows(
                st.g_UK[j : j + 1], np.asarray([j])
            )[0])
        else:
            ovf = 0
        return cost, ovf

    def best_solution(self) -> Solution:
        st, j = self.group.st, self.j
        return decode_chain_items(
            st.probs[j], st.g_items[j], st.g_counts[j],
            st.g_kinds[j] if st.hetero else None,
        )

    def migrate_in(self, sol: Solution) -> bool:
        st, j = self.group.st, self.j
        return self.packer._block_migrate(st, j, sol)

    def trace(self) -> list:
        st, j = self.group.st, self.j
        return st.traces[j]

    def offset(self, t0: float) -> float:
        return self.group.st.t_start - t0

    def iterations(self) -> int:
        st, j, c = self.group.st, self.j, self.packer.n_chains
        return int(st.steps[j * c : (j + 1) * c].sum())

    def truncated(self) -> bool:
        """True iff the fleet stopped on the wall-clock cap — done, but
        neither frozen (patience) nor out of iteration budget."""
        if self.eliminated:
            return False
        st = self.group.st
        return st.done and not st.frozen and st.it < self.packer.max_iterations


class _GAGroup:
    """All GA islands, advanced in lockstep with stacked fitness calls.

    ``mesh`` row-shards each stacked fitness call over the ``("prob",)``
    sweep mesh — execution shape only, bit-identical (PR 8)."""

    def __init__(self, pairs, mesh=None):
        self.pairs = pairs  # [(packer, run)] in island order
        self.mesh = mesh

    def advance(self, limit: int | None) -> bool:
        progressed = False
        while lockstep_generation(self.pairs, gen_limit=limit, mesh=self.mesh):
            progressed = True
        return progressed


class _GAIsland:
    def __init__(self, packer: GeneticPacker, run):
        self.packer = packer
        self.run = run
        self.eliminated = False

    def done(self) -> bool:
        # exhausted patience counts as done even before the next lockstep
        # call marks it (mirrors _ScalarIsland: no migrants for converged runs)
        return self.run.done or self.run.stale >= self.packer.patience

    def extend(self, gen_limit: int) -> None:
        self.packer._extend_run(self.run, gen_limit)

    def eliminate(self) -> None:
        self.packer._eliminate_run(self.run)
        self.eliminated = True

    def raw(self) -> tuple[int, int]:
        cost = int(self.run.best_cost)
        ovf = int(self.run.best.inventory_overflow()) if self.run.hetero else 0
        return cost, ovf

    def best_solution(self) -> Solution:
        return self.run.best

    def migrate_in(self, sol: Solution) -> bool:
        return self.packer._migrate_in(self.run, sol)

    def trace(self) -> list:
        return self.run.trace

    def offset(self, t0: float) -> float:
        return self.run.t0 - t0

    def iterations(self) -> int:
        return self.run.gen

    def truncated(self) -> bool:
        return (
            not self.eliminated
            and self.run.done
            and self.run.gen < self.packer.max_generations
            and self.run.stale < self.packer.patience
        )


class _ScalarIsland:
    """A scalar-loop or single-chain SA island (its own resumable state)."""

    def __init__(self, packer: SimulatedAnnealingPacker, st, single: bool):
        self.packer = packer
        self.st = st
        self.single = single
        self.eliminated = False

    def extend(self, it_limit: int) -> None:
        hook = (
            self.packer._single_extend if self.single
            else self.packer._scalar_extend
        )
        hook(self.st, it_limit)

    def eliminate(self) -> None:
        self.packer._loop_eliminate(self.st)
        self.eliminated = True

    def advance(self, limit: int | None) -> bool:
        if self.st.done:
            return False
        before = self.st.it
        run = self.packer._single_run if self.single else self.packer._scalar_run
        run(self.st, limit)
        return self.st.it > before

    def done(self) -> bool:
        return self.st.done or self.st.stale >= self.packer.patience

    def raw(self) -> tuple[int, int]:
        return int(self.st.best_cost), int(self.st.best_ovf)

    def best_solution(self) -> Solution:
        return self.st.best

    def migrate_in(self, sol: Solution) -> bool:
        hook = (
            self.packer._single_migrate if self.single
            else self.packer._scalar_migrate
        )
        return hook(self.st, sol)

    def trace(self) -> list:
        return self.st.trace

    def offset(self, t0: float) -> float:
        return self.st.t_start - t0

    def iterations(self) -> int:
        return self.st.it

    def truncated(self) -> bool:
        return (
            not self.eliminated
            and self.st.done
            and self.st.it < self.packer.max_iterations
            and self.st.stale < self.packer.patience
        )


def _merge_traces(parts: list[tuple[float, list]]) -> list:
    """Global monotone best-so-far trace across (offset, trace) parts."""
    events: list[tuple[float, float]] = []
    for offset, tr in parts:
        events.extend((offset + t, cc) for t, cc in tr)
    events.sort()
    merged: list = []
    best = None
    for t, cc in events:
        if best is None or cc < best:
            best = cc
            merged.append((t, cc))
    return merged


def _sa_fleet_key(packer: SimulatedAnnealingPacker, resolved: str) -> tuple:
    """Engine signature under which sa-s islands share one fleet: everything
    that shapes the array program except the seed (per-island RNG streams
    keep differently-seeded islands independent inside one fleet)."""
    return (
        resolved, packer.n_chains, packer.t0, packer.rc, packer.swap_moves,
        packer.p_adm_w, packer.p_adm_h, packer.intra_layer,
        packer.max_iterations, packer.patience, packer.max_seconds,
        packer.exchange_every, packer.ladder_min, packer.ladder_max,
        packer.p_kind, packer.inventory_penalty,
    )


def _family_stride(family: str, interval: int, ga_islands: int) -> int:
    """Barrier stride (iterations/generations per barrier) of one engine
    family — ``"ga"``, ``"scalar"`` (sa-nfd's sequential repack / the
    legacy backend), or ``"delta"`` (fleet and single-chain sa-s) — on a
    heterogeneous lineup; see `_GA_STRIDE_DIV` above.  ``ga_islands`` (the
    lineup's GA island count) scales the SA strides so the delta-kernel
    engines keep annealing for roughly the wall time one stacked GA
    generation takes, instead of idling at the barrier."""
    if family == "ga":
        return max(1, interval // _GA_STRIDE_DIV)
    mult = max(1, ga_islands)
    if family == "scalar":
        return max(1, interval // _SCALAR_STRIDE_DIV) * mult
    return interval * mult


def _group_stride(group, interval: int, ga_islands: int) -> int:
    """`_family_stride` of one built engine group."""
    if isinstance(group, _GAGroup):
        family = "ga"
    elif isinstance(group, _ScalarIsland) and not group.single:
        family = "scalar"
    else:
        family = "delta"
    return _family_stride(family, interval, ga_islands)


def _island_family(packer, resolved: str) -> str:
    """The `_family_stride` family a packer's island lands in."""
    if isinstance(packer, GeneticPacker):
        return "ga"
    if packer.perturbation == "nfd" or resolved == "legacy":
        return "scalar"
    return "delta"


def _island_work(packer, family: str, stride: int) -> int:
    """Ledger units (chain-annealing-step equivalents, see `_GA_GEN_WORK`)
    one island burns per barrier."""
    if family == "ga":
        return stride * packer.n_pop * _GA_GEN_WORK
    if family == "delta" and packer.n_chains > 1:
        return stride * packer.n_chains
    return stride


def _lineup_work(packers, resolved, interval: int) -> int:
    """Total ledger work the given lineup would consume running every
    island to its configured iteration/generation budget (rounded up to
    whole barriers) — the racing driver's "equal total budget" anchor:
    ``pack_portfolio(auto=True)`` defaults its ledger to the default
    lineup's `_lineup_work`, so auto-tuning never spends more than the
    lineup it replaces."""
    fams = [_island_family(p, r) for p, r in zip(packers, resolved)]
    n_ga = fams.count("ga")
    fleet_keys = {
        _sa_fleet_key(p, r)
        for p, r, f in zip(packers, resolved, fams)
        if f == "delta" and p.n_chains > 1
    }
    # group count mirrors pack_portfolio's construction: one GA lockstep
    # pack, one group per distinct fleet signature, one per scalar island
    n_groups = (
        (1 if n_ga else 0)
        + len(fleet_keys)
        + sum(1 for p, f in zip(packers, fams)
              if f == "scalar" or (f == "delta" and p.n_chains == 1))
    )
    multi = n_groups > 1
    seg = interval if interval > 0 else DEFAULT_MIGRATION_EVERY
    total = 0
    for p, f in zip(packers, fams):
        s = _family_stride(f, seg, n_ga) if (multi and interval > 0) else seg
        budget = p.max_generations if f == "ga" else p.max_iterations
        barriers = -(-int(budget) // s)  # ceil: whole-barrier accounting
        total += barriers * _island_work(p, f, s)
    return total


class _Race:
    """Successive-halving race state over the portfolio's island adapters.

    The ledger (``budget``, in `_island_work` units) is split evenly over
    ``halvings + 1`` phases; each time a phase's share is spent the worse
    half of the surviving islands is eliminated (penalized best cost,
    first island wins ties) until ``final_k`` remain, and the rest of the
    ledger — including everything the eliminated islands never ran — is
    spent advancing the survivors further (docs/DESIGN.md section 16).
    Every decision is a pure function of island trajectories and the
    static work weights, so races are bit-reproducible and the state
    round-trips through the portfolio checkpoint payload."""

    def __init__(self, work: list[int], budget: int, final_k: int):
        self.work = [int(w) for w in work]
        self.budget = int(budget)
        self.final_k = max(1, int(final_k))
        n = len(work)
        self.halvings = 0
        s = n
        while s > self.final_k:
            s = max(self.final_k, (s + 1) // 2)
            self.halvings += 1
        self.phase_budget = max(1, self.budget // (self.halvings + 1))
        self.alive = [True] * n
        self.spent = 0
        self.rung = 0
        self.rung_spent = 0
        self.eliminated: list[dict] = []

    def live(self, adapters) -> list[int]:
        """Islands still racing AND still able to advance (not frozen)."""
        return [
            k for k, isl in enumerate(adapters)
            if self.alive[k] and not isl.done()
        ]

    def charge(self, live: list[int]) -> bool:
        """Burn one barrier's work for ``live``; False when the ledger
        cannot cover it (the race is over — never overspends)."""
        cost = sum(self.work[k] for k in live)
        if cost <= 0 or self.spent + cost > self.budget:
            return False
        self.spent += cost
        self.rung_spent += cost
        return True

    def maybe_halve(self, adapters, barrier: int, lam: float) -> None:
        """At a rung boundary (this phase's ledger share is spent), keep
        the best half of the surviving islands and eliminate the rest."""
        if self.rung >= self.halvings or self.rung_spent < self.phase_budget:
            return
        self.rung += 1
        self.rung_spent = 0
        racing = [k for k in range(len(adapters)) if self.alive[k]]
        keep = max(self.final_k, (len(racing) + 1) // 2)
        if keep >= len(racing):
            return
        vals = {
            k: (lambda c, o: c + lam * o)(*adapters[k].raw()) for k in racing
        }
        ranked = sorted(racing, key=lambda k: (vals[k], k))
        for k in ranked[keep:]:
            self.alive[k] = False
            adapters[k].eliminate()
            self.eliminated.append(
                {"island": k, "barrier": int(barrier), "value": float(vals[k])}
            )

    def state(self) -> dict:
        """JSON-able snapshot payload (checkpoint codec)."""
        return {
            "budget": self.budget,
            "spent": self.spent,
            "rung": self.rung,
            "rung_spent": self.rung_spent,
            "eliminated": self.eliminated,
        }

    def restore(self, state: dict, adapters) -> None:
        """Re-enter a checkpointed race: replay the recorded eliminations
        onto the freshly restored adapters (idempotent — the engine states
        in the snapshot are already frozen/stopped) and resume the ledger."""
        self.spent = int(state["spent"])
        self.rung = int(state["rung"])
        self.rung_spent = int(state["rung_spent"])
        self.eliminated = [dict(e) for e in state["eliminated"]]
        for e in self.eliminated:
            k = int(e["island"])
            self.alive[k] = False
            adapters[k].eliminate()


def _group_label(group, i: int) -> str:
    if isinstance(group, _SAFleetGroup):
        return f"g{i}:fleet"
    if isinstance(group, _GAGroup):
        return f"g{i}:ga"
    return f"g{i}:single" if group.single else f"g{i}:scalar"


def _group_backend(group) -> str:
    """The evaluation backend a group's kernel calls resolved to (the scalar
    loop calls no kernel: ``"python"``)."""
    if isinstance(group, _SAFleetGroup):
        return group.st.backend
    if isinstance(group, _GAGroup):
        return group.pairs[0][1].backend
    return group.st.backend if group.single else "python"


def _timed_advance(group, limit) -> tuple[bool, float]:
    """Side-lane unit of work: advance one group to its barrier limit and
    report (progressed, seconds).  Groups share no mutable state and each
    island consumes only its own RNG stream, so running these on a thread
    pool is bit-identical to the serial loop."""
    t = time.perf_counter()
    progressed = group.advance(limit)
    return progressed, time.perf_counter() - t


def _pump(gen, d_e):
    """Feed one delta-cost answer into a `_block_gen` step generator."""
    try:
        return gen.send(d_e)
    except StopIteration:
        return None


def _advance_fused(
    fleet: "_SAFleetGroup", ga: "_GAGroup", fleet_limit, ga_limit
) -> tuple[bool, bool]:
    """Advance the SA fleet and the GA lockstep pack *together*, answering
    one fleet step request and one stacked GA generation's fitness batch
    through a single ``binpack_portfolio_step`` device program whenever
    both have work (odd cycles — fleet drained, GA still running, or a
    multi-population-size lineup — fall back to the separate kernels).

    Bit-parity holds by construction: the fused kernel returns exactly the
    totals/deltas the separate ``binpack_fitness`` / ``binpack_sa_step``
    calls would (exact integer arithmetic, pinned in tests), and each
    engine still consumes only its own RNG stream in its own order.
    Returns (fleet_progressed, ga_progressed)."""
    from repro.kernels.binpack_portfolio_step.ops import portfolio_step

    packer, st = fleet.packer, fleet.st
    before = st.it
    gen = None if st.done else packer._block_gen(st, fleet_limit)
    req = next(gen, None) if gen is not None else None
    ga_progressed = False
    while True:
        advanced, batches = lockstep_begin(ga.pairs, ga_limit)
        if req is None and not advanced:
            break
        if req is not None and len(batches) == 1:
            batch = batches[0]
            W, H, Km = stack_geometry([r for _, r, _ in batch])
            old_w, old_h, new_w, new_h, old_k, new_k = req
            totals, d_e = portfolio_step(
                W, H, old_w, old_h, new_w, new_h,
                modes=st.modes0, backend=st.backend,
                kinds=Km, old_k=old_k, new_k=new_k,
                kind_tables=st.kt if old_k is not None else None,
                mesh=st.mesh,
            )
            lockstep_apply(batch, totals)
            batches = []
            req = _pump(gen, d_e)
        elif req is not None:
            req = _pump(gen, packer._block_eval(st, req))
        for batch in batches:
            lockstep_apply(
                batch,
                stacked_population_costs(
                    [r for _, r, _ in batch], batch[0][1].backend,
                    mesh=ga.mesh,
                ),
            )
        if lockstep_finish(advanced):
            ga_progressed = True
    return st.it > before, ga_progressed


def pack_portfolio(
    prob: PackingProblem,
    islands: Sequence[IslandSpec] | None = None,
    n_islands: int = 4,
    algorithms: Sequence[str] = ("ga-nfd", "sa-s", "sa-nfd"),
    seed: int = 0,
    max_seconds: float = 30.0,
    migration_every: int | None = None,
    intra_layer: bool = False,
    backend: str = "auto",
    max_workers: int | None = None,
    sa_chains: int = 8,
    scheduler: str = "concurrent",
    fused: bool | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    on_checkpoint=None,
    mesh=None,
    auto: bool = False,
    race_grid=None,
    race_budget: int | None = None,
    race_final: int = 2,
    **hyper,
) -> PackingResult:
    """Run K differently-seeded islands as one fleet; return the best result.

    **Self-tuning portfolio (racing).**  ``auto=True`` replaces the fixed
    lineup with a successive-halving hyperparameter race: every config in
    ``race_grid`` (default `DEFAULT_RACE_GRID` — chain counts, temperature
    ladders, population sizes, mutation rates; entries are ``(algorithm,
    hyper-overrides)`` pairs or full `IslandSpec`s, seeded ``seed + k``)
    starts as an island, and at migration barriers the race ledger decides
    who keeps running.  The ledger (``race_budget``, in chain-annealing-step
    equivalents — see `_island_work`) defaults to exactly the total work the
    *default* lineup (``n_islands`` islands cycling ``algorithms``) would
    consume under the same iteration/generation budgets, so auto-tuning
    never spends more than the lineup it replaces.  The ledger is split
    evenly over ``log2(N / race_final) + 1`` phases; at each phase boundary
    the worse half of the surviving islands (penalized best cost, first
    island wins ties) is eliminated — elimination just stops advancing the
    island (a fleet member freezes, a GA run is marked done), so survivors'
    RNG streams are untouched — and the freed budget is *reallocated*: the
    survivors' engine budgets are extended barrier by barrier until the
    ledger is spent.  Races are bit-reproducible, machine-independent, and
    checkpoint/resume-safe like any other portfolio run (the race state
    rides the snapshot payload); ``params["race"]`` records the ledger,
    the eliminations, and the survivors.  Per-island ``max_iterations`` /
    ``max_generations`` only anchor the default ledger — the race itself
    extends survivors past them by design (patience still freezes islands,
    and ``max_seconds`` stays the outer safety cap).
    Racing semantics: docs/DESIGN.md section 16.

    ``islands`` gives full control; otherwise ``n_islands`` specs are derived
    by cycling ``algorithms`` with seeds ``seed, seed+1, ...``.  ``hyper``
    accepts the same Table-2 names as :func:`repro.core.api.pack` and applies
    to every island (per-island ``IslandSpec.hyper`` overrides win).

    ``migration_every`` is an **iteration/generation count** (default 64,
    `DEFAULT_MIGRATION_EVERY`): each barrier advances the delta-kernel SA
    islands that many annealing steps, then broadcasts the global best into
    every other live island's worst warm slot.  On heterogeneous lineups
    each engine family advances at its own per-family stride (GA islands
    ``migration_every // 32`` generations and scalar loops
    ``migration_every // 4`` iterations per barrier, min 1; the
    delta-kernel SA strides scale with the lineup's GA island count — see
    `_group_stride`): strides are static functions of the lineup only, so
    trajectories stay machine-independent, and no family's segment can
    park the barrier (docs/DESIGN.md section 13).  Pass ``migration_every=0`` to disable
    migration (islands run independently to their budgets).
    ``max_seconds`` is an outer safety cap only — for bit-reproducible,
    machine-independent runs give the islands iteration budgets
    (``max_iterations`` / ``max_generations``) and a large ``max_seconds``,
    exactly as with :func:`repro.core.api.pack_sweep`.

    ``scheduler`` picks how groups advance *between* barriers:
    ``"concurrent"`` (default) runs the device-dispatch lane (the SA fleet,
    fused with the GA pack when ``fused`` engages) on the calling thread
    and every other engine group on a `ThreadPoolExecutor` side lane;
    ``"serial"`` is the PR-5 reference loop.  Both schedules are
    **bit-identical** — groups share no mutable state and each island
    consumes only its own RNG stream, so concurrency changes wall-clock,
    never results (pinned in ``tests/test_portfolio_concurrent.py``).
    ``fused=None`` (auto) routes each barrier's SA fleet step requests and
    stacked GA fitness batch through one combined
    ``binpack_portfolio_step`` device program when both engines resolved to
    a jax backend ("ref"/"pallas"); ``True``/``False`` force it.  On a CPU
    host SA auto-resolves to host numpy, so auto keeps fused dispatch off
    there.

    A "sa-s" island runs the batched multi-chain annealer with ``sa_chains``
    temperature-laddered chains; all such islands advance as ONE
    `_anneal_block` fleet (K islands x C chains of problem-major rows), so
    the portfolio's SA work is a single vectorized array program.  A
    single-island portfolio is bit-identical to the standalone
    ``pack(prob, algorithm, seed=...)`` run — same engines, same RNG
    streams, no migration.

    Heterogeneous device scenarios need no extra wiring: build the problem
    with an inventory (``get_problem(name, device="U280")``) and every
    island explores RAM-kind lanes under the shared inventory penalty —
    migrated solutions carry their kind lanes with them, and the ``p_kind``
    / ``inventory_penalty`` hyperparameters pass through like any Table-2
    name.

    ``max_workers`` is deprecated and ignored: the fleet-native portfolio
    has no thread pool (see :func:`pack_portfolio_threads` for the legacy
    engine, kept as a benchmark baseline).

    Scaling past one device (PR 8, docs/DESIGN.md section 14): ``mesh`` (a
    :func:`repro.launch.mesh.make_sweep_mesh` device mesh) row-shards the
    sa-s fleet's annealing steps and the GA pack's stacked fitness calls
    over its ``("prob",)`` axis.  It is an execution-shape knob only: every
    mesh is **bit-identical** to the default single-device run (pinned in
    ``tests/test_sharded.py``).

    Crash safety (docs/DESIGN.md section 12): with ``checkpoint_dir`` the
    run cuts a durable snapshot of every island's engine state (plus the
    barrier/migration counters) every ``checkpoint_every`` migration
    barriers; ``resume=True`` restarts from the newest *intact* snapshot
    and — because barrier segmentation never changes trajectories — lands
    on a result bit-identical to an uninterrupted same-seed run (pinned by
    ``tests/test_resume.py``).  ``max_seconds`` is not part of the
    checkpoint identity, so a preempted run may resume under a fresh wall
    budget.  ``on_checkpoint(step)`` fires after each durable write.

    If the wall-clock cap cuts any island short of its iteration/patience
    budget, the result's ``params["truncated_by_wallclock"]`` is True and a
    ``RuntimeWarning`` is emitted (``params["barriers"]`` records how many
    migration barriers completed) — a truncated portfolio is NOT
    bit-reproducible across machines.

    Wall-clock attribution lands in the result's params:
    ``params["barrier_seconds"]`` is the per-barrier wall time and
    ``params["group_seconds"]`` maps each engine group (``"g0:ga"``,
    ``"g1:fleet"``, ``"g2:scalar"``, ...; a fused pair reports as
    ``"g0+g1:fused"``) to its cumulative advance seconds, so the bench can
    see where a lineup's time goes.  Timing keys are diagnostics only and
    exempt from the bit-reproducibility contract.
    """
    from .api import make_packer  # late import: api imports nothing from here

    if max_workers is not None:
        warnings.warn(
            "pack_portfolio(max_workers=...) is deprecated and ignored: the "
            "portfolio is fleet-native (no thread pool); use "
            "pack_portfolio_threads for the legacy engine",
            DeprecationWarning,
            stacklevel=2,
        )
    if not auto and (race_grid is not None or race_budget is not None):
        raise ValueError("race_grid/race_budget require auto=True")
    if n_islands < 1:
        raise ValueError("n_islands must be >= 1")
    default_specs = [
        IslandSpec(algorithm=algorithms[k % len(algorithms)], seed=seed + k)
        for k in range(n_islands)
    ]
    if auto:
        if islands is not None:
            raise ValueError(
                "pass auto=True (with race_grid=...) or islands=..., not both"
            )
        grid = DEFAULT_RACE_GRID if race_grid is None else list(race_grid)
        islands = [
            entry if isinstance(entry, IslandSpec)
            else IslandSpec(algorithm=entry[0], seed=seed + k,
                            hyper=dict(entry[1]))
            for k, entry in enumerate(grid)
        ]
    elif islands is None:
        islands = default_specs
    islands = list(islands)
    if not islands:
        raise ValueError("portfolio needs at least one island")
    interval = (
        DEFAULT_MIGRATION_EVERY if migration_every is None
        else int(migration_every)
    )
    ck = None
    if checkpoint_dir is not None:
        from .resume import PortfolioCheckpointer, portfolio_config_key

        ck = PortfolioCheckpointer(
            checkpoint_dir,
            portfolio_config_key(
                prob, islands, interval, intra_layer, backend, sa_chains,
                hyper,
                race=(
                    (int(race_budget) if race_budget is not None else None,
                     int(race_final))
                    if auto else None
                ),
            ),
            every=checkpoint_every, resume=resume, on_checkpoint=on_checkpoint,
        )
    hetero = prob.n_kinds > 1
    t0 = time.perf_counter()

    # --- build islands; group sa-s fleets, GA lockstep pairs, scalar loops
    packers = [
        make_packer(
            spec.algorithm,
            seed=spec.seed,
            max_seconds=max_seconds,
            intra_layer=intra_layer,
            backend=backend,
            **{
                **({"n_chains": sa_chains} if spec.algorithm == "sa-s" else {}),
                **hyper,
                **spec.hyper,
            },
        )
        for spec in islands
    ]
    # cross-island ranking weight for the global best: the portfolio-level
    # override if given, else the strictest island's penalty (per-island
    # IslandSpec.hyper overrides may differ; ranking under the max keeps a
    # feasible packing outranking an overflowing one for every island)
    lam = (
        float(hyper["inventory_penalty"])
        if "inventory_penalty" in hyper
        else max(float(p.inventory_penalty) for p in packers)
    )
    adapters: list = [None] * len(islands)
    groups: list = []
    ga_pairs: list = []
    fleet_members: dict[tuple, list] = {}  # fleet key -> [(k, packer)]
    for k, packer in enumerate(packers):
        if isinstance(packer, GeneticPacker):
            b = packer._resolve_backend()
            run = packer._start_run(
                prob, np.random.default_rng(packer.seed), None, b
            )
            totals = (
                packer._batched_costs(
                    run.W, run.H, b, run.Km, run.kt, run.modes0, mesh=mesh
                )
                if run.batched
                else None
            )
            packer._eval_init(run, totals)
            ga_pairs.append((packer, run))
            adapters[k] = _GAIsland(packer, run)
            continue
        resolved = packer._resolve_backend()
        packer._hetero = hetero
        if packer.perturbation == "nfd" or resolved == "legacy":
            st = packer._scalar_start(prob, None)
            isl = _ScalarIsland(packer, st, single=False)
            groups.append(isl)
            adapters[k] = isl
        elif packer.n_chains == 1:
            st = packer._single_start(prob, None, resolved)
            isl = _ScalarIsland(packer, st, single=True)
            groups.append(isl)
            adapters[k] = isl
        else:
            fleet_members.setdefault(_sa_fleet_key(packer, resolved), []).append(
                (k, packer)
            )
    if ga_pairs:
        groups.append(_GAGroup(ga_pairs, mesh=mesh))
    for members in fleet_members.values():
        fleet = _SAFleetGroup(
            members[0][1],
            prob,
            [np.random.default_rng(p.seed) for _, p in members],
            members[0][1]._resolve_backend(),
            mesh=mesh,
        )
        groups.append(fleet)
        for j, (k, _) in enumerate(members):
            adapters[k] = _FleetIsland(fleet, j)

    # --- barriered fleet loop: advance everything, then migrate
    if scheduler not in ("concurrent", "serial"):
        raise ValueError(
            f"unknown scheduler {scheduler!r}; options: concurrent, serial"
        )
    barrier = 0
    migrations = 0
    truncated = False
    single = len(adapters) == 1
    if ck is not None:
        restored = ck.restore_groups(groups)
        if restored is not None:
            barrier, migrations = restored
    # with checkpointing, runs that would otherwise advance in one
    # unbounded call (single island, or migration disabled) still pause at
    # DEFAULT_MIGRATION_EVERY-iteration barriers purely to cut snapshots —
    # barrier segmentation never changes trajectories (PR-5 contract)
    seg = interval if interval > 0 else (
        DEFAULT_MIGRATION_EVERY if (ck is not None or auto) else 0
    )
    # per-family strides rebalance heterogeneous lineups (see the module
    # constants); homogeneous lineups and snapshot-only segmentation keep
    # the uniform stride.  Strides are deterministic functions of the
    # lineup and ``migration_every``, so they are part of the trajectory
    # contract; ``scheduler``/``fused`` are not (dispatch only).
    multi = len(groups) > 1
    n_ga_islands = len(ga_pairs)
    strides = [
        _group_stride(g, seg, n_ga_islands) if (multi and interval > 0)
        else seg
        for g in groups
    ]
    labels = [_group_label(g, i) for i, g in enumerate(groups)]
    # --- racing state: static work weights, the ledger, and (on resume)
    # the replayed eliminations
    race = None
    agroup: list[int] = []
    members_of: list[list[int]] = [[] for _ in groups]
    if auto:
        gi_of = {id(g): i for i, g in enumerate(groups)}
        ga_gi = next(
            (i for i, g in enumerate(groups) if isinstance(g, _GAGroup)), None
        )
        work: list[int] = []
        for k, isl in enumerate(adapters):
            if isinstance(isl, _FleetIsland):
                g, fam = gi_of[id(isl.group)], "delta"
            elif isinstance(isl, _GAIsland):
                g, fam = ga_gi, "ga"
            else:
                g = gi_of[id(isl)]
                fam = "scalar" if not isl.single else "delta"
            agroup.append(g)
            members_of[g].append(k)
            work.append(_island_work(isl.packer, fam, strides[g]))
        if race_budget is None:
            # equal total budget vs the lineup auto replaces: the default
            # ``n_islands`` lineup's work under the same budget knobs
            dpackers = [
                make_packer(
                    spec.algorithm, seed=spec.seed, max_seconds=max_seconds,
                    intra_layer=intra_layer, backend=backend,
                    **{
                        **({"n_chains": sa_chains}
                           if spec.algorithm == "sa-s" else {}),
                        **hyper,
                    },
                )
                for spec in default_specs
            ]
            race_budget = _lineup_work(
                dpackers, [p._resolve_backend() for p in dpackers], interval
            )
        race = _Race(work, race_budget, race_final)
        if ck is not None and ck.race is not None:
            race.restore(ck.race, adapters)
    # the fused pair: the (only) SA fleet group + the GA lockstep pack,
    # merged into one main-thread dispatch unit when both engines resolved
    # to a jax backend (forced either way via ``fused``)
    fi = next(
        (i for i, g in enumerate(groups) if isinstance(g, _SAFleetGroup)), None
    )
    gi = next(
        (i for i, g in enumerate(groups) if isinstance(g, _GAGroup)), None
    )
    fuse = (
        scheduler == "concurrent" and fi is not None and gi is not None
        and sum(isinstance(g, _SAFleetGroup) for g in groups) == 1
        and (
            fused if fused is not None
            else (
                groups[fi].st.backend in ("ref", "pallas")
                and all(r.backend in ("ref", "pallas") and r.batched
                        for _, r in groups[gi].pairs)
            )
        )
    )
    # main-thread lane: the fused pair, else the SA fleet (device dispatch
    # window), else the first group; everything else rides the side lane
    main_idx = {fi, gi} if fuse else {fi if fi is not None else 0}
    side_idx = [i for i in range(len(groups)) if i not in main_idx]
    pool = (
        ThreadPoolExecutor(max_workers=len(side_idx))
        if scheduler == "concurrent" and side_idx
        else None
    )
    group_seconds: dict[str, float] = {lab: 0.0 for lab in labels}
    if fuse:
        fused_label = f"g{min(fi, gi)}+g{max(fi, gi)}:fused"
        group_seconds[fused_label] = 0.0
        for i in sorted(main_idx):
            group_seconds.pop(labels[i])
    barrier_seconds: list[float] = []
    try:
        # racing gates the loop itself: a budget-done survivor is revived by
        # the extension below, so only the race's live/ledger checks (or the
        # wall cap) may end an auto run
        while race is not None or any(not isl.done() for isl in adapters):
            if barrier > 0 and time.perf_counter() - t0 > max_seconds:
                truncated = True
                break
            t_bar = time.perf_counter()
            unbounded = race is None and ((single and ck is None) or seg <= 0)
            limits = [
                None if unbounded else (barrier + 1) * s for s in strides
            ]
            idle: frozenset = frozenset()
            if race is not None:
                # extend every surviving island's engine budget to this
                # barrier's limit FIRST (reallocation is just a larger
                # it_limit — it revives islands that stopped on budget,
                # funded by the work the eliminated islands never ran),
                # then let the ledger gate the barrier
                for k, isl in enumerate(adapters):
                    if race.alive[k]:
                        isl.extend(limits[agroup[k]])
                live = race.live(adapters)
                if not live:
                    break  # every survivor frozen or wall-capped
                if not race.charge(live):
                    break  # ledger spent: the race is over
                # eliminated islands vacate their lane: a group with no
                # live member is never dispatched (its states are inert, so
                # skipping it cannot perturb survivors' RNG streams)
                idle = frozenset(
                    i for i, members in enumerate(members_of)
                    if all(adapters[k].done() for k in members)
                )
            barrier += 1
            progressed = [False] * len(groups)
            if pool is not None:
                futures = {
                    i: pool.submit(_timed_advance, groups[i], limits[i])
                    for i in side_idx
                    if i not in idle
                }
            else:
                futures = {}
            t_main = time.perf_counter()
            if fuse:
                progressed[fi], progressed[gi] = _advance_fused(
                    groups[fi], groups[gi], limits[fi], limits[gi]
                )
                group_seconds[fused_label] += time.perf_counter() - t_main
            else:
                mains = sorted(main_idx) if pool is not None else [
                    i for i in range(len(groups)) if i not in futures
                ]
                for i in mains:
                    if i in idle:
                        continue
                    progressed[i], dt = _timed_advance(groups[i], limits[i])
                    group_seconds[labels[i]] += dt
            for i, fut in futures.items():
                progressed[i], dt = fut.result()
                group_seconds[labels[i]] += dt
            if not single and interval > 0:
                # deterministic migration: strict-min global best (first
                # island wins ties) lands in every OTHER live island's
                # worst warm slot
                vals = [
                    c + lam * o for c, o in (isl.raw() for isl in adapters)
                ]
                src = min(range(len(vals)), key=vals.__getitem__)
                migrant = adapters[src].best_solution()
                for k, isl in enumerate(adapters):
                    if k != src:
                        migrations += isl.migrate_in(migrant)
            if race is not None:
                race.maybe_halve(adapters, barrier, lam)
            if ck is not None and barrier % ck.every == 0:
                ck.save_groups(
                    groups, barrier, migrations,
                    race=race.state() if race is not None else None,
                )
            barrier_seconds.append(time.perf_counter() - t_bar)
            if not any(progressed):
                break  # no island can move: budgets exhausted mid-barrier
    finally:
        if pool is not None:
            pool.shutdown()

    # --- assemble the portfolio result (strict-min, first island wins ties)
    wall = time.perf_counter() - t0
    # the outer cap above, or any island's own engine hitting its wall cap
    # short of its iteration/patience budget, silently breaks seed-level
    # reproducibility — surface it instead (satellite of DESIGN.md sec. 12)
    truncated = truncated or any(isl.truncated() for isl in adapters)
    if truncated:
        warnings.warn(
            f"pack_portfolio stopped on wall-clock after {barrier} "
            "barrier(s) before the islands' iteration/patience budgets; the "
            "result is NOT seed-reproducible (params['truncated_by_wallclock']"
            " is True). Give islands iteration budgets for reproducible runs.",
            TruncationWarning,
            stacklevel=2,
        )
    raws = [isl.raw() for isl in adapters]
    vals = [c + lam * o for c, o in raws]
    best_k = min(range(len(vals)), key=vals.__getitem__)
    best_sol = adapters[best_k].best_solution()
    best_cost = raws[best_k][0]
    trace = _merge_traces([(isl.offset(t0), isl.trace()) for isl in adapters])
    trace.append((wall, vals[best_k] if hetero else best_cost))
    names = "+".join(p.name for p in packers)
    return PackingResult(
        solution=best_sol,
        cost=int(best_cost),
        efficiency=best_sol.efficiency(),
        wall_time_s=wall,
        algorithm=f"portfolio[{names}]" + ("-intra" if intra_layer else ""),
        trace=trace,
        iterations=sum(isl.iterations() for isl in adapters),
        params=dict(
            islands=[
                dict(algorithm=s.algorithm, seed=s.seed, **s.hyper) for s in islands
            ],
            barriers=barrier,
            migration_every=interval,
            migrations=migrations,
            truncated_by_wallclock=truncated,
            backend=backend,
            seed=seed,
            scheduler=scheduler,
            fused=bool(fuse),
            backends={lab: _group_backend(g) for lab, g in zip(labels, groups)},
            interpret=any(pallas_interpret(_group_backend(g)) for g in groups),
            strides=dict(zip(labels, strides)),
            barrier_seconds=barrier_seconds,
            group_seconds=group_seconds,
            **(
                dict(race=dict(
                    budget=race.budget,
                    spent=race.spent,
                    halvings=race.halvings,
                    phase_budget=race.phase_budget,
                    final_k=race.final_k,
                    work=list(race.work),
                    survivors=[
                        k for k, a in enumerate(race.alive) if a
                    ],
                    eliminated=race.eliminated,
                ))
                if race is not None else {}
            ),
        ),
    )


# ---------------------------------------------------- legacy thread portfolio
class _Island:
    """A packer plus its warm state, advanced one budgeted round at a time
    (the legacy thread-pool portfolio's unit of work)."""

    def __init__(self, prob: PackingProblem, spec: IslandSpec, packer):
        self.prob = prob
        self.spec = spec
        self.packer = packer
        self.is_ga = isinstance(packer, GeneticPacker)
        self.pop: list[Solution] | None = None  # GA warm population
        self.chains: list[Solution] | None = None  # SA warm incumbents (1/chain)

    def run_round(self, budget_s: float, round_idx: int) -> PackingResult:
        self.packer.max_seconds = budget_s
        self.packer.seed = self.spec.seed + _ROUND_SEED_STRIDE * round_idx
        if self.is_ga:
            result = self.packer.pack(self.prob, init_pop=self.pop)
            self.pop = self.packer.last_population_
        else:
            result = self.packer.pack(self.prob, init=self.chains)
            self.chains = self.packer.last_chains_
        return result

    def migrate_in(self, best: Solution, best_val: float, score) -> None:
        """The global best replaces this island's worst warm individual/chain
        (``score`` is the inventory-penalized cost on heterogeneous problems,
        the plain cost otherwise)."""
        warm = self.pop if self.is_ga else self.chains
        if not warm:
            return
        worst = max(range(len(warm)), key=lambda i: score(warm[i]))
        if score(warm[worst]) > best_val:
            warm[worst] = best.copy()


def pack_portfolio_threads(
    prob: PackingProblem,
    islands: Sequence[IslandSpec] | None = None,
    n_islands: int = 4,
    algorithms: Sequence[str] = ("ga-nfd", "sa-s", "sa-nfd"),
    seed: int = 0,
    max_seconds: float = 30.0,
    migration_every: float | None = None,
    intra_layer: bool = False,
    backend: str = "auto",
    max_workers: int | None = None,
    sa_chains: int = 8,
    **hyper,
) -> PackingResult:
    """The legacy thread-pool portfolio, kept as the benchmark baseline.

    K islands evolve concurrently on a thread pool under one shared
    wall-clock budget, synchronizing every ``migration_every`` *seconds*
    (default ``max_seconds / 4``) to migrate the global best.  Rounds are
    wall-clock budgeted, so results vary with machine speed and load —
    exactly the nondeterminism the fleet-native :func:`pack_portfolio`
    replaced (``benchmarks/run.py --only portfolio`` compares the two).

    **Baseline only.**  This engine is kept solely as the comparison point
    for the bench lineup matrix and ``tools/portfolio_gate.py``; it is
    outside the determinism, checkpoint/resume, and scheduler contracts
    and intentionally grows no ``scheduler``/``fused``/``checkpoint_dir``
    surface (pinned by ``tests/test_portfolio_concurrent.py``).  Use
    :func:`pack_portfolio` for real runs.
    """
    from .api import make_packer  # late import: api imports nothing from here

    if islands is None:
        if n_islands < 1:
            raise ValueError("n_islands must be >= 1")
        islands = [
            IslandSpec(algorithm=algorithms[k % len(algorithms)], seed=seed + k)
            for k in range(n_islands)
        ]
    if not islands:
        raise ValueError("portfolio needs at least one island")
    pool = [
        _Island(
            prob,
            spec,
            make_packer(
                spec.algorithm,
                seed=spec.seed,
                max_seconds=max_seconds,
                intra_layer=intra_layer,
                backend=backend,
                **{
                    **({"n_chains": sa_chains} if spec.algorithm == "sa-s" else {}),
                    **hyper,
                    **spec.hyper,
                },
            ),
        )
        for spec in islands
    ]
    interval = migration_every if migration_every is not None else max_seconds / 4.0
    interval = max(interval, 1e-3)

    # island comparisons use the inventory-penalized cost on heterogeneous
    # problems so a feasible packing always outranks an overflowing one
    hetero = prob.n_kinds > 1
    lam = hyper.get("inventory_penalty", DEFAULT_INVENTORY_PENALTY)
    if hetero:
        def score(sol: Solution) -> float:
            return sol.cost() + lam * sol.inventory_overflow()
    else:
        def score(sol: Solution) -> float:
            return sol.cost()

    t0 = time.perf_counter()
    rounds: list[tuple[float, list[PackingResult]]] = []
    best_sol: Solution | None = None
    best_cost = 0
    best_val = 0.0
    iterations = 0
    round_idx = 0
    with ThreadPoolExecutor(max_workers=max_workers or len(pool)) as ex:
        while True:
            elapsed = time.perf_counter() - t0
            remaining = max_seconds - elapsed
            if round_idx > 0 and remaining <= 1e-3:
                break
            budget = min(interval, max(remaining, 1e-3))
            futures = [
                ex.submit(isl.run_round, budget, round_idx) for isl in pool
            ]
            results = [f.result() for f in futures]
            rounds.append((elapsed, results))
            for r in results:
                iterations += r.iterations
                val = score(r.solution)
                if best_sol is None or val < best_val:
                    best_sol, best_cost, best_val = r.solution, r.cost, val
            for isl in pool:
                isl.migrate_in(best_sol, best_val, score)
            round_idx += 1
    wall = time.perf_counter() - t0
    trace = _merge_traces(
        [(offset, r.trace) for offset, results in rounds for r in results]
    )
    trace.append((wall, best_cost))
    names = "+".join(isl.packer.name for isl in pool)
    return PackingResult(
        solution=best_sol,
        cost=int(best_cost),
        efficiency=best_sol.efficiency(),
        wall_time_s=wall,
        algorithm=f"portfolio-threads[{names}]" + ("-intra" if intra_layer else ""),
        trace=trace,
        iterations=iterations,
        params=dict(
            islands=[
                dict(algorithm=s.algorithm, seed=s.seed, **s.hyper) for s in islands
            ],
            rounds=round_idx,
            migration_every=interval,
            backend=backend,
            seed=seed,
        ),
    )
