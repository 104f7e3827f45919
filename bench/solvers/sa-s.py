"""SA-S, the packer's multi-chain annealer: its settings, budget, warm-up,
reference replay and control, for the harness (``bench/harness.py``
``solver_file``)."""
from __future__ import annotations

import numpy as np

from bench.reference import replay_sa_s

HYPER = ("sa_t0", "sa_rc", "p_adm_w", "p_adm_h", "swap_moves", "exchange_every",
         "ladder_min", "ladder_max", "p_kind")


def settings(cfg: dict) -> dict:
    """``pack`` / ``pack_sweep`` / ``solve_batch`` keyword arguments: the
    solver block, ``n_chains`` chains of ``max_iterations`` steps, patience
    off (the harness sets the wall cap off)."""
    s = {k: v for k, v in cfg["solver"].items()
         if k not in ("algorithm", "patience", "max_seconds")}
    return dict(s, n_chains=int(cfg["n_chains"]),
                max_iterations=int(cfg["max_iterations"]), patience=10**12)


def budget(cfg: dict) -> int:
    """Chain steps every answer reports."""
    return int(cfg["n_chains"]) * int(cfg["max_iterations"])


def warm(cfg: dict):
    """(settings the warm-up overrides, a function that compiles the step
    kernel at the fleet's row count for each number of tasks one call
    solves)."""
    width = 2 * max(int(cfg["solver"]["swap_moves"]), 1)
    chains = int(cfg["n_chains"])

    def compile_kernel(problems, tasks, backend):
        from repro.kernels.binpack_sa_step.ops import sa_step_deltas

        prob = problems[0]
        for k in tasks:
            z = np.zeros((chains * k, width), dtype=np.int32)
            if prob.n_kinds > 1:
                sa_step_deltas(z, z, z, z, backend=backend, old_k=z, new_k=z,
                               kind_tables=prob.kind_tables)
            else:
                sa_step_deltas(z, z, z, z, modes=prob.kind_tables[0][1], backend=backend)

    return {"max_iterations": 1}, compile_kernel


def replay(ref, seed: int, cfg: dict, penalty: float | None = None) -> dict:
    """The reference's SA-S answer for ``seed``."""
    s = cfg["solver"]
    return replay_sa_s(ref, seed, int(cfg["n_chains"]), int(cfg["max_iterations"]),
                       penalty=s["inventory_penalty"] if penalty is None else penalty,
                       **{k: s[k] for k in HYPER})


def control(ref, seed: int, cfg: dict) -> dict:
    """The reference annealed without the inventory penalty: its acceptance
    ignores the device's RAM counts.  It parts from the program only where
    a count binds (RN152 on the U50, five of the eight accelerators on the
    ZU7EV); on a one-kind or unbounded problem it answers as the program
    does and would pass the check."""
    return replay(ref, seed, cfg, penalty=0.0)
