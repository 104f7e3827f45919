"""The control: the plain reference put in the program's place, with one
piece of the solver's work left out, the host-side work a faster step would
be tempted to drop.  Each solver's file says which (``control`` in
``bench/solvers/<algorithm>.py``): SA-S anneals without the inventory
penalty, GA-NFD selects without the layer term of its fitness.  Every check
replays the largest problem, so the control's packings and traces part from
the program's.  The control's answers go through the same check as the
program's, which has to find them not correct.

    python3 -m bench.control --workload <cell> --seed <n> --seconds <s>

runs one window of the cell with the control answering and prints the check
numbers; the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import types
from pathlib import Path

from bench import harness


def _solve(cfg: dict, rules, ref, prob, seed: int, on_chip: bool):
    """The control's answer, in the program's result type (the service
    stores and serves that type)."""
    from repro.core.problem import PackingResult, Solution

    out = rules.control(ref, int(seed), cfg)
    sol = Solution(prob, out["bins"], kinds=out["kinds"])
    return PackingResult(
        solution=sol, cost=out["cost"], efficiency=sol.efficiency(), wall_time_s=0.0,
        algorithm="control", trace=[(0.0, c) for c in out["trace"]],
        iterations=out["iterations"],
        params={"backend": "pallas", "interpret": not on_chip, "seed": int(seed)},
    )


@contextlib.contextmanager
def in_place_of_program(cfg: dict, on_chip: bool):
    """Answer the program's entry points (``pack``, ``pack_sweep``,
    ``solve_batch``) with the solver's control."""
    import repro.core as core
    from repro.core import dse

    rules = harness.solver_file(cfg["solver"]["algorithm"])
    by_name = {r.name: r for r in harness.reference_problems(cfg)}

    def pack(prob, algorithm, seed=0, **_):
        return _solve(cfg, rules, by_name[prob.name], prob, seed, on_chip)

    def solve_batch(problems, algorithm=None, seeds=None, **_):
        return [_solve(cfg, rules, by_name[p.name], p, s, on_chip)
                for p, s in zip(problems, seeds)]

    def pack_sweep(problems, algorithm, seeds=None, **_):
        return types.SimpleNamespace(results=solve_batch(problems, seeds=seeds))

    saved = (core.pack, core.pack_sweep, dse.solve_batch)
    core.pack, core.pack_sweep, dse.solve_batch = pack, pack_sweep, solve_batch
    try:
        yield
    finally:
        core.pack, core.pack_sweep, dse.solve_batch = saved


def run_control(cell: harness.Cell, seed: int, seconds: float, allow_cpu=False,
                backend="auto") -> dict:
    import jax

    on_chip = jax.devices()[0].platform == "tpu"
    with in_place_of_program(cell.config, on_chip):
        return harness.run(cell, seed, seconds, False, time.time(),
                           allow_cpu=allow_cpu, backend=backend)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    sys.path.insert(0, str(root / "src"))
    line = run_control(harness.load_cell(root, args.workload), args.seed, args.seconds)
    print(json.dumps({"correct": line["correct"], "attempted": line["attempted"],
                      "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
