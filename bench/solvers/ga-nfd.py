"""GA-NFD, the paper's genetic algorithm with the NFD repack as its
mutation: its settings, budget, warm-up, reference replay and control, for
the harness (``bench/harness.py`` ``solver_file``)."""
from __future__ import annotations

import numpy as np

from bench.reference import replay_ga_nfd

HYPER = ("n_tour", "p_mut", "p_adm_w", "p_adm_h", "nfd_threshold", "nfd_extra_frac",
         "nfd_max_bins", "layer_weight")


def settings(cfg: dict) -> dict:
    """``pack`` / ``pack_sweep`` / ``solve_batch`` keyword arguments: the
    solver block, ``max_generations`` generations, patience off (the harness
    sets the wall cap off).  A device of more than one RAM kind is refused:
    the replay covers one kind."""
    from bench.harness import CellError

    if any(len(d["kinds"]) != 1 for d in cfg["devices"].values()):
        raise CellError("the GA-NFD replay covers one-kind devices only")
    s = {k: v for k, v in cfg["solver"].items()
         if k not in ("algorithm", "patience", "max_seconds")}
    return dict(s, max_generations=int(cfg["max_generations"]), patience=10**12)


def budget(cfg: dict) -> int:
    """Generations every answer reports."""
    return int(cfg["max_generations"])


def warm(cfg: dict):
    """(settings the warm-up overrides, a function that compiles the
    fitness kernel at the population's shape, ``n_pop`` rows a task by one
    column per buffer, for each problem and each number of tasks of one
    problem that a call solves)."""
    n_pop = int(cfg["solver"]["n_pop"])

    def compile_kernel(problems, tasks, backend):
        from repro.kernels.binpack_fitness.ops import population_costs

        for prob in problems:
            for k in tasks:
                z = np.zeros((n_pop * k, prob.n), dtype=np.int32)
                if prob.n_kinds > 1:
                    population_costs(z, z, backend=backend, kinds=z,
                                     kind_tables=prob.kind_tables)
                else:
                    population_costs(z, z, modes=prob.kind_tables[0][1], backend=backend)

    return {"max_generations": 1}, compile_kernel


def replay(ref, seed: int, cfg: dict, layer_weight: float | None = None) -> dict:
    """The reference's GA-NFD answer for ``seed``."""
    s = dict(cfg["solver"])
    if layer_weight is not None:
        s["layer_weight"] = layer_weight
    return replay_ga_nfd(ref, seed, int(s["n_pop"]), int(cfg["max_generations"]),
                         **{k: s[k] for k in HYPER})


def control(ref, seed: int, cfg: dict) -> dict:
    """The reference with the layer term left out of its fitness: selection
    on the cost alone, the host-side work a faster generation would be
    tempted to drop.  Tournaments between packings of one cost then go
    another way, and the trajectory parts from the program's."""
    return replay(ref, seed, cfg, layer_weight=0.0)
