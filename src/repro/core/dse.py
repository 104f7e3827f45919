"""Cross-problem batched DSE solver: pack a *fleet* of problems in one run.

The paper's motivating use-case (section 2.3) is memory packing inside a
design-space-exploration inner loop: every (network x folding x device x
precision) candidate needs a packed OCM estimate, and sweeps span hundreds
of candidates per accelerator build (the authors' sequel, arXiv:2011.07317).
Solving candidates one at a time leaves the batched kernels — which already
vectorize over chains and populations *within* one problem — idle across
the problem axis.  :func:`pack_sweep` closes that gap:

* Candidates are deduplicated by :meth:`PackingProblem.fingerprint` (and
  optionally served from a caller-owned ``cache`` dict), so repeated DSE
  candidates are free.
* The remaining fleet is grouped by cost-model signature
  (:func:`problem.batch_group_key`) and each group is padded to a common
  ``(NB, max_items)`` envelope (:func:`problem.encode_problem_batch`).
* ``sa-s`` groups run the multi-problem chain-block annealer
  (`SimulatedAnnealingPacker._anneal_block`): P problems x C chains advance
  in lock-step as one ``(P*C, ...)`` array program, with per-problem
  temperature ladders, best tracking, and early-exit freezing of converged
  problems.  Each problem consumes its own RNG stream, so its result is
  **bit-identical** to a standalone ``pack(prob, "sa-s", n_chains=C,
  seed=...)`` run — batching buys throughput, never different answers.
* ``ga-nfd``/``ga-s`` groups run a *lockstep* driver over the GA's phase
  helpers: mutations stay per-problem Python, but every generation's
  population fitness is evaluated in ONE leading-problem-axis
  ``binpack_fitness`` call over the stacked ``(P, n_pop, NB)`` matrices.
  Again bit-identical per problem to standalone runs.
* Everything else (``sa-nfd``, single-chain SA, ``legacy`` backends, the
  one-shot heuristics, ``portfolio``) falls back to a serial per-problem
  loop through :func:`api.pack` — same results, no batching.

Budget semantics: ``max_seconds`` is the wall-clock budget of one engine
*invocation* — a batched group shares one clock (its problems advance
together), the serial lane spends it per problem.  For reproducible,
parity-testable sweeps prefer iteration budgets (``max_iterations`` /
``max_generations`` with a huge ``max_seconds``), which freeze each problem
at exactly the same trajectory point as its standalone run.

Axes, padding, and masking contracts: docs/DESIGN.md section 10; the
paper-concept-to-code map lives in docs/ALGORITHMS.md.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import numpy as np

from .ga import (
    lockstep_apply,
    lockstep_begin,
    lockstep_finish,
    stacked_population_costs,
)
from .problem import (
    PackingProblem,
    PackingResult,
    batch_group_key,
)

# algorithms whose batched lane exists (everything else runs serially)
_SA_BATCHED = ("sa-s",)
_GA_LOCKSTEP = ("ga-nfd", "ga-s")


def normalize_hyper(algorithm: str, hyper: dict) -> dict:
    """Apply the sweep-level hyperparameter defaults for ``algorithm``.

    ``pack_sweep`` gives ``sa-s`` fleets ``n_chains=8`` unless told
    otherwise; anything that derives task identities for sweep-solved work
    (the serve layer's request keys, ``ResultStore`` entries) must normalize
    the same way or identical requests would hash to different tasks.
    """
    out = dict(hyper)
    if algorithm.lower() in _SA_BATCHED:
        out.setdefault("n_chains", 8)
    return out


def task_key(
    prob: PackingProblem,
    algorithm: str,
    seed: int,
    intra_layer: bool = False,
    backend: str = "auto",
    max_seconds: float = 30.0,
    hyper: dict | None = None,
) -> tuple:
    """Stable identity of one solve: everything that can change its answer.

    Two requests with equal keys are interchangeable — same problem
    fingerprint, algorithm, seed, and settings — so they may share one
    result object (``pack_sweep`` dedups on this; ``repro.serve`` coalesces
    in-flight duplicates and keys its persistent store on it).  Callers
    passing ``hyper`` should run it through :func:`normalize_hyper` first
    if they want keys comparable with ``pack_sweep``'s.
    """
    hkey = tuple(sorted((k, repr(v)) for k, v in (hyper or {}).items()))
    return (
        prob.fingerprint(), algorithm.lower(), int(seed), bool(intra_layer),
        backend, float(max_seconds), hkey,
    )


# --------------------------------------------------------------- sweep result
@dataclasses.dataclass
class SweepResult:
    """Outcome of one :func:`pack_sweep` call.

    ``results[i]`` is the :class:`PackingResult` of ``problems[i]`` —
    positions with equal task fingerprints share one result object.
    ``fresh`` holds the positions that were actually solved this call (the
    rest came from the fingerprint dedup or the caller's ``cache``).
    """

    results: list[PackingResult]
    problems: list[PackingProblem]
    wall_time_s: float
    n_solved: int
    cache_hits: int
    n_groups: int
    algorithm: str
    fresh: tuple[int, ...] = ()
    #: sweep-level counters (PR 8): ``solved`` unique tasks solved this call,
    #: ``cache_hits`` unique tasks served from the cache / checkpoint store,
    #: ``dedup_hits`` positions collapsed by fingerprint dedup (so
    #: ``solved + cache_hits + dedup_hits == len(problems)``).
    params: dict = dataclasses.field(default_factory=dict)

    @property
    def size(self) -> int:
        return len(self.results)

    @property
    def candidates_per_sec(self) -> float:
        """Aggregate DSE throughput: candidates scored per wall second."""
        return self.size / max(self.wall_time_s, 1e-9)

    def costs(self) -> np.ndarray:
        return np.asarray([r.cost for r in self.results], dtype=np.int64)

    def pareto_indices(self) -> list[int]:
        """Non-dominated candidates over (cost down, Eq.-1 efficiency up).

        Across a sweep of *different* workloads this is the standard DSE
        screen: a candidate survives unless another candidate stores its
        bits at least as efficiently in no more RAM.  Callers with a real
        throughput model should build their own front from ``results``.
        """
        cost = self.costs()
        eff = np.asarray([r.efficiency for r in self.results])
        out = []
        for i in range(self.size):
            dominated = np.any(
                (cost <= cost[i]) & (eff >= eff[i])
                & ((cost < cost[i]) | (eff > eff[i]))
            )
            if not dominated:
                out.append(i)
        return out

    def table(self) -> str:
        """Efficiency/Pareto report, one row per candidate."""
        pareto = set(self.pareto_indices())
        fresh = set(self.fresh)
        lines = [
            f"{'#':>3} {'candidate':<24} {'bufs':>5} {'baseline':>9} "
            f"{'packed':>7} {'dBRAM':>6} {'eff%':>6} {'ovf':>5} {'src':>6} "
            f"{'pareto':>6}"
        ]
        for i, (prob, r) in enumerate(zip(self.problems, self.results)):
            ovf = r.solution.inventory_overflow()
            lines.append(
                f"{i:>3} {prob.name[:24]:<24} {prob.n:>5} "
                f"{prob.baseline_cost():>9} {r.cost:>7} "
                f"{r.baseline_cost / max(r.cost, 1):>6.2f} "
                f"{r.efficiency * 100:>6.1f} {ovf:>5} "
                f"{'solve' if i in fresh else 'cache':>6} "
                f"{'*' if i in pareto else '':>6}"
            )
        lines.append(self.summary())
        return "\n".join(lines)

    def summary(self) -> str:
        return (
            f"sweep[{self.algorithm}]: {self.size} candidates in "
            f"{self.wall_time_s:.2f}s ({self.candidates_per_sec:.2f}/s), "
            f"{self.n_solved} solved fresh in {self.n_groups} group(s), "
            f"{self.cache_hits} served from dedup/cache"
        )


def _task_keys(problems, algorithm, seeds, intra_layer, backend,
               max_seconds, hyper) -> list[tuple]:
    return [
        task_key(prob, algorithm, s, intra_layer, backend, max_seconds, hyper)
        for prob, s in zip(problems, seeds)
    ]


def _group_by_cost_model(indices, problems) -> list[list[int]]:
    """One group per cost-model signature — deliberately NOT sub-chunked by
    size: per-step work in the batched engines is dominated by
    ``(P*C, touched)``-shaped operations that barely see the padded
    envelope, so one big group amortizes the fixed per-step overhead best
    (measured: chunking a 16-candidate Table-1 fleet into 4 size-banded
    groups cut the speedup from ~4.5x to ~2.7x).  Grouping never changes
    results — each problem consumes its own RNG stream and padding never
    affects trajectories."""
    groups: dict = {}
    for i in indices:
        groups.setdefault(batch_group_key(problems[i]), []).append(i)
    return list(groups.values())


def _solve_sa_groups(
    packer, groups, problems, seeds, backend, keys=None, ck=None, mesh=None,
) -> dict[int, PackingResult]:
    out: dict[int, PackingResult] = {}
    for group in groups:
        probs = [problems[i] for i in group]
        rngs = [np.random.default_rng(seeds[i]) for i in group]
        packer._hetero = probs[0].n_kinds > 1
        if ck is None:
            blocks = packer._anneal_block(
                probs, rngs, [[] for _ in group], backend, mesh=mesh
            )
        else:
            # checkpointed lane: same start/run/finish phases, but paused at
            # iteration barriers for durable snapshots.  Barrier segmentation
            # never changes trajectories (the PR-5 resumable-engine contract),
            # so results stay bit-identical to the uncheckpointed lane.
            from .resume import encode_block_state, group_digest

            gd = group_digest([keys[i] for i in group])
            st = packer._block_start(
                probs, rngs, [[] for _ in group], backend, mesh=mesh
            )
            ck.restore_block(gd, st)  # overwrite from snapshot if it matches
            while not st.done:
                packer._block_run(st, (st.it // ck.every + 1) * ck.every)
                if not st.done:
                    arrays, extra = encode_block_state(st)
                    ck.save_progress(group=gd, arrays=arrays, engine=extra)
            blocks = packer._block_finish(st)
        for i, blk in zip(group, blocks):
            packer.seed = seeds[i]  # per-problem seed lands in result params
            out[i] = packer._result(
                blk.best, blk.best_cost, blk.wall, blk.trace,
                blk.iterations, backend, uphill=blk.uphill,
            )
            if ck is not None:
                ck.mark_done(keys[i], out[i])
        if ck is not None:
            ck.save_progress()  # group complete: results only, no engine state
    return out


def _lockstep_drain(pairs, gen_limit=None, mesh=None) -> bool:
    """One lockstep generation through the GA segment API — identical to
    ``ga.lockstep_generation`` (which wraps the same phases), written out so
    the sweep lane exercises the begin/apply/finish contract the portfolio's
    fused barrier dispatch builds on."""
    advanced, batches = lockstep_begin(pairs, gen_limit)
    for batch in batches:
        lockstep_apply(
            batch,
            stacked_population_costs(
                [r for _, r, _ in batch], batch[0][1].backend, mesh=mesh
            ),
        )
    return lockstep_finish(advanced)


def _solve_ga_groups(
    packer, groups, problems, seeds, backend, keys=None, ck=None, mesh=None,
) -> dict[int, PackingResult]:
    out: dict[int, PackingResult] = {}
    for group in groups:
        runs = [
            packer._start_run(
                problems[i], np.random.default_rng(seeds[i]), None, backend
            )
            for i in group
        ]
        totals = stacked_population_costs(runs, backend, mesh=mesh)
        for run, tot in zip(runs, totals):
            packer._eval_init(run, tot)
        # drive the GA segment API directly (ga.lockstep_begin / apply /
        # finish): per generation, one mutation phase across every live run,
        # one stacked fitness call per population-size batch, then
        # selection — the same phases the fleet-native portfolio fuses with
        # SA work at its barriers (docs/DESIGN.md section 13).
        pairs = [(packer, run) for run in runs]
        if ck is None:
            while _lockstep_drain(pairs, mesh=mesh):
                pass
        else:
            from .resume import encode_ga_group, group_digest

            gd = group_digest([keys[i] for i in group])
            ck.restore_ga_group(gd, runs)
            while True:
                live = [run.gen for run in runs if not run.done]
                if not live:
                    break
                glimit = (min(live) // ck.every + 1) * ck.every
                while _lockstep_drain(pairs, glimit, mesh=mesh):
                    pass
                if all(run.done for run in runs):
                    break
                arrays, extras = encode_ga_group(runs)
                ck.save_progress(group=gd, arrays=arrays, engine=extras)
        for i, run in zip(group, runs):
            packer.seed = seeds[i]  # per-problem seed lands in result params
            out[i] = packer._finish_run(run)
            if ck is not None:
                ck.mark_done(keys[i], out[i])
        if ck is not None:
            ck.save_progress()
    return out


def _solve_positions(
    todo, problems, seeds, algorithm, *, seed=0, max_seconds=30.0,
    intra_layer=False, backend="auto", keys=None, ck=None, mesh=None,
    hyper=None,
) -> tuple[dict[int, PackingResult], int]:
    """Solve the given positions of ``problems`` through the right lane.

    The shared lane dispatcher behind :func:`pack_sweep` (which feeds it
    the deduplicated representatives) and :func:`solve_batch` (which feeds
    it everything).  Returns ``({position: result}, n_groups)``.
    """
    from .api import make_packer, pack as _pack  # late: api re-exports us

    hyper = hyper or {}
    solved: dict[int, PackingResult] = {}
    todo = sorted(todo)
    if not todo:
        return solved, 0
    if algorithm in _SA_BATCHED or algorithm in _GA_LOCKSTEP:
        packer = make_packer(
            algorithm, seed=seed, max_seconds=max_seconds,
            intra_layer=intra_layer, backend=backend, **hyper,
        )
        resolved = packer._resolve_backend()
    else:
        packer = resolved = None
    if (
        algorithm in _SA_BATCHED
        and resolved != "legacy"
        and packer.n_chains > 1
    ):
        groups = _group_by_cost_model(todo, problems)
        solved = _solve_sa_groups(
            packer, groups, problems, seeds, resolved, keys=keys, ck=ck,
            mesh=mesh,
        )
    elif algorithm in _GA_LOCKSTEP and resolved in ("ref", "pallas"):
        groups = _group_by_cost_model(todo, problems)
        solved = _solve_ga_groups(
            packer, groups, problems, seeds, resolved, keys=keys, ck=ck,
            mesh=mesh,
        )
    else:
        # serial fallback: scalar/legacy engines, heuristics, portfolio.
        # Checkpoint granularity here is whole candidates: each finished
        # solve is durable, an in-flight one restarts from scratch.
        groups = [[i] for i in todo]
        for i in todo:
            solved[i] = _pack(
                problems[i], algorithm, seed=seeds[i],
                max_seconds=max_seconds, intra_layer=intra_layer,
                backend=backend, **hyper,
            )
            if ck is not None:
                ck.mark_done(keys[i], solved[i])
                ck.save_progress()
    return solved, len(groups)


def solve_batch(
    problems: Sequence[PackingProblem],
    algorithm: str = "sa-s",
    seed: int = 0,
    seeds: Sequence[int] | None = None,
    max_seconds: float = 30.0,
    intra_layer: bool = False,
    backend: str = "auto",
    mesh=None,
    **hyper,
) -> list[PackingResult]:
    """Solve one micro-batch of problems as a single batched fleet.

    The reusable single-batch entry point behind the serving layer
    (``repro.serve.PackingService`` executes every flushed micro-batch
    through this on its worker lane): no dedup, no caching, no
    checkpointing — just the lane dispatch of :func:`pack_sweep` applied to
    *every* position, returning one :class:`PackingResult` per problem in
    order.  Callers should pre-group compatible problems with
    :func:`repro.core.problem.batch_group_key` when they want exactly one
    fleet per call; mixed batches still work (they split into one group per
    cost model).  Results carry the same bit-parity guarantee as
    ``pack_sweep``: each is identical to the standalone
    ``pack(problems[i], algorithm, seed=seeds[i], ...)`` run.
    """
    problems = list(problems)
    if not problems:
        raise ValueError("solve_batch needs at least one problem")
    algorithm = algorithm.lower()
    if seeds is None:
        seeds = [seed] * len(problems)
    else:
        seeds = [int(s) for s in seeds]
        if len(seeds) != len(problems):
            raise ValueError("seeds must align with problems")
    hyper = normalize_hyper(algorithm, hyper)
    solved, _ = _solve_positions(
        range(len(problems)), problems, seeds, algorithm, seed=seed,
        max_seconds=max_seconds, intra_layer=intra_layer, backend=backend,
        mesh=mesh, hyper=hyper,
    )
    return [solved[i] for i in range(len(problems))]


def pack_sweep(
    problems: Sequence[PackingProblem],
    algorithm: str = "sa-s",
    seed: int = 0,
    seeds: Sequence[int] | None = None,
    max_seconds: float = 30.0,
    intra_layer: bool = False,
    backend: str = "auto",
    cache: dict | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 256,
    resume: bool = False,
    on_checkpoint=None,
    mesh=None,
    **hyper,
) -> SweepResult:
    """Solve a fleet of packing problems in one vectorized run.

    Parameters mirror :func:`api.pack` (the paper's Table-2 hyperparameter
    names pass through ``hyper``), applied to every candidate:

    * ``problems`` — the DSE candidates; duplicates (by
      :meth:`PackingProblem.fingerprint` + seed + settings) are solved once.
    * ``seed`` / ``seeds`` — one base seed for all candidates (the default,
      which maximizes dedup), or an explicit per-candidate seed list.
    * ``intra_layer`` — forbid mixing layers within a bin, as in the
      paper's intra-layer packing scenario (applies fleet-wide).
    * ``backend`` — evaluation backend, as in :func:`api.pack`; the batched
      lanes need a non-``legacy`` backend and otherwise fall back to the
      serial loop.
    * ``cache`` — optional caller-owned dict carrying solutions across
      sweeps; hits skip solving entirely (the DSE outer loop revisits
      candidates constantly).
    * ``algorithm="sa-s"`` (the default) gets ``n_chains=8`` unless given;
      each candidate's result is bit-identical to the standalone
      ``pack(prob, algorithm, seed=..., n_chains=...)`` run, so batching
      changes throughput only — never answers (pinned in
      ``tests/test_dse.py``).

    Crash safety (docs/DESIGN.md section 12): with ``checkpoint_dir`` the
    sweep cuts a durable snapshot every ``checkpoint_every`` engine
    iterations/generations (plus one per completed group) — completed
    candidates and the in-flight batched group's full engine state.
    ``resume=True`` restarts from the newest *intact* snapshot (corrupt or
    torn steps are skipped) and, because every engine is deterministic from
    any barrier state, lands on results **bit-identical** to an
    uninterrupted same-seed run (pinned by ``tests/test_resume.py``).
    ``on_checkpoint(step)`` fires after each durable write (the
    fault-injection hook).  Resumed-from-checkpoint candidates count as
    cache hits, not fresh solves.

    Scaling past one device (PR 8, docs/DESIGN.md section 14): ``mesh`` —
    a 1-D ``("prob",)`` device mesh
    (:func:`repro.launch.mesh.make_sweep_mesh`) — row-shards each batched
    kernel step over its devices via ``shard_map``, bit-identically (pinned
    in ``tests/test_sharded.py``).  Jax backends ("ref"/"pallas") only; the
    ``"python"`` backend and the serial fallback lane ignore it.
    """
    problems = list(problems)
    if not problems:
        raise ValueError("pack_sweep needs at least one problem")
    algorithm = algorithm.lower()
    if seeds is None:
        seeds = [seed] * len(problems)
    else:
        seeds = [int(s) for s in seeds]
        if len(seeds) != len(problems):
            raise ValueError("seeds must align with problems")
    hyper = normalize_hyper(algorithm, hyper)
    t_start = time.perf_counter()

    keys = _task_keys(problems, algorithm, seeds, intra_layer, backend,
                      max_seconds, hyper)
    ck = None
    if checkpoint_dir is not None:
        from .resume import SweepCheckpointer, sweep_config_key

        ck = SweepCheckpointer(
            checkpoint_dir, sweep_config_key(keys), every=checkpoint_every,
            resume=resume, on_checkpoint=on_checkpoint,
        )
    results_by_key: dict[tuple, PackingResult] = {}
    if cache is not None:
        for k in set(keys):
            if k in cache:
                results_by_key[k] = cache[k]
    if ck is not None:
        # candidates completed before the crash are served, not re-solved
        for i, k in enumerate(keys):
            if k not in results_by_key:
                prev = ck.result_for(k, problems[i])
                if prev is not None:
                    results_by_key[k] = prev
    rep: dict[tuple, int] = {}  # first position of each unsolved unique task
    for i, k in enumerate(keys):
        if k not in results_by_key and k not in rep:
            rep[k] = i
    fresh = tuple(sorted(rep.values()))
    cache_hits = len(problems) - len(fresh)

    # --- lane dispatch for the unsolved representatives
    n_groups = 0
    if rep:
        solved, n_groups = _solve_positions(
            rep.values(), problems, seeds, algorithm, seed=seed,
            max_seconds=max_seconds, intra_layer=intra_layer,
            backend=backend, keys=keys, ck=ck, mesh=mesh, hyper=hyper,
        )
        for i, res in solved.items():
            results_by_key[keys[i]] = res
            if cache is not None:
                cache[keys[i]] = res

    return SweepResult(
        results=[results_by_key[k] for k in keys],
        problems=problems,
        wall_time_s=time.perf_counter() - t_start,
        n_solved=len(fresh),
        cache_hits=cache_hits,
        n_groups=n_groups,
        algorithm=algorithm,
        fresh=fresh,
        params=dict(
            solved=len(fresh),
            cache_hits=len(set(keys)) - len(fresh),
            dedup_hits=len(problems) - len(set(keys)),
        ),
    )
