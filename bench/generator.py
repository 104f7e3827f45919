"""The one traffic generator: every mix is a data file under bench/traffic/.

A mix names its ``entry`` (``pack``, ``sweep`` or ``serve``) and the
parameters this module reads.  Solver seeds, and the sample of answers
the check replays, come from the run's ``--seed`` through ``numpy``'s
``SeedSequence``, so the same seed gives the same inputs; an open loop's
arrival schedule comes from a fixed stream and is the same for every seed.

The arrival arithmetic (exponential gaps at a fixed rate, Zipf popularity
over problem ranks) is copied from the program's ``serve/traffic.py``
``make_workload``, with two changes: a request repeats an earlier task of
the run with probability ``revisit`` (else it carries a fresh seed), and
arrivals stop at the window's end instead of at a request count.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

SEED_SPACE = 2**31  # solver seeds are drawn below this


def _rng(seed: int, stream: str) -> np.random.Generator:
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, tag]))


class SolverSeeds:
    """Endless stream of solver seeds for a closed loop."""

    def __init__(self, seed: int, stream: str = "solver-seeds"):
        self._rng = _rng(seed, stream)

    def take(self, n: int) -> list[int]:
        return [int(s) for s in self._rng.integers(0, SEED_SPACE, size=n)]


@dataclass(frozen=True)
class Arrival:
    due_s: float  # offset from the window's start
    problem: int  # index into the configuration's problem list
    seed: int  # solver seed
    repeat: bool  # repeats an earlier task of this run


def arrivals(seed: int, seconds: float, n_problems: int, rate_hz: float,
             zipf_a: float, revisit: float) -> list[Arrival]:
    """Poisson arrivals in ``[0, seconds)``; Zipf popularity by list order,
    uniform picks at ``zipf_a`` 0.

    The schedule (each request's due time, its problem, and for a repeat
    which earlier task it repeats) comes from a fixed stream and is the
    same for every seed; the seed draws the solver seeds of the fresh
    tasks.  An open loop's latencies hang on the order of its requests and
    not only on their set, so this is what keeps runs with different seeds
    offering the same work, as runs of one seed do."""
    fixed = _rng(0, "arrival-set")
    ranks = np.arange(1, n_problems + 1, dtype=np.float64)
    popularity = ranks**-zipf_a
    popularity /= popularity.sum()
    gaps, t = [], 0.0
    while True:
        gap = fixed.exponential(1.0 / rate_hz)
        if t + gap >= seconds:
            break
        gaps.append(gap)
        t += gap
    n = len(gaps)
    picks = fixed.choice(n_problems, size=n, p=popularity)
    repeats = fixed.random(n) < revisit
    if n:
        repeats[0] = False  # the first request has nothing to repeat

    rng = _rng(seed, "arrivals")
    out: list[Arrival] = []
    due = 0.0
    for i in range(n):
        due += float(gaps[i])
        if repeats[i]:
            prev = out[int(fixed.integers(i))]
            out.append(Arrival(due, prev.problem, prev.seed, True))
        else:
            out.append(Arrival(due, int(picks[i]), int(rng.integers(0, SEED_SPACE)), False))
    return out


def sample(seed: int, n_items: int, k: int, must: int | None = None) -> list[int]:
    """``k`` distinct indices out of ``n_items`` drawn from the seed, always
    holding ``must`` (the answer to the largest problem)."""
    rng = _rng(seed, "check-sample")
    picked = [int(i) for i in rng.permutation(n_items)[:k]]
    if must is not None and must not in picked:
        picked[-1:] = [must]
    return sorted(picked)


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the ``ceil(q * n)``-th smallest."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    idx = min(len(xs) - 1, max(0, int(np.ceil(q * len(xs))) - 1))
    return float(xs[idx])
