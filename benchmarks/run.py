"""Benchmark harness — one function per paper table/figure + the TPU
adaptation and roofline tables.  Prints name,value CSVs (see each module).

  python -m benchmarks.run                   # everything (tens of minutes)
  python -m benchmarks.run --only table4     # one table
  python -m benchmarks.run --only portfolio  # fleet vs thread portfolio
  python -m benchmarks.run --quick           # reduced budgets (CI-scale)
  python -m benchmarks.run --smoke           # execute every bench module in
                                             # seconds (rot check, no numbers)
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="engine|hetero|sa|portfolio|racing|dse|sweep_sharded|"
                         "serve|table3|table4|fig45|tpu|seqpack|kernels|"
                         "roofline")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problems, 1-2 iterations, no meaningful "
                         "numbers — exercises every bench entry point so "
                         "they cannot rot unnoticed")
    args = ap.parse_args(argv)

    from . import (
        bench_dse,
        bench_engine,
        bench_fig45,
        bench_kernels,
        bench_racing,
        bench_roofline,
        bench_seqpack,
        bench_serve,
        bench_sweep_sharded,
        bench_table3,
        bench_table4,
        bench_tpu_packing,
    )
    from .common import BUDGETS, SEEDS

    quick, smoke = args.quick, args.smoke
    # per-mode knobs for the modules without their own smoke/quick switches
    # (bench_engine.run* and bench_dse.run take quick=/smoke= directly)
    if smoke:
        budgets = {k: 1 for k in BUDGETS}
        small = ["CNV-W1A1"]
        t3_seeds = (0,)
        fig_kw = dict(budget_s=0.5, seeds=(0,))
        tpu_kw = dict(archs=["hymba-1.5b"], budget_s=0.3)
        n_docs = 80
    else:
        budgets = {k: max(3, v // 4) for k, v in BUDGETS.items()} if quick else None
        small = (
            ["CNV-W1A1", "CNV-W2A2", "Tincy-YOLO", "RN50-W1A2"] if quick else None
        )
        t3_seeds = SEEDS
        fig_kw = dict(budget_s=8 if quick else 25)
        tpu_kw = dict(budget_s=2 if quick else 5)
        n_docs = 500 if quick else 2000

    jobs = {
        "engine": lambda: (
            bench_engine.run(quick=quick, smoke=smoke),
            bench_engine.run_hetero(quick=quick, smoke=smoke),
        ),
        "hetero": lambda: bench_engine.run_hetero(quick=quick, smoke=smoke),
        "sa": lambda: bench_engine.run_sa(quick=quick, smoke=smoke),
        "portfolio": lambda: bench_engine.run_portfolio(quick=quick, smoke=smoke),
        "racing": lambda: bench_racing.run(quick=quick, smoke=smoke),
        "dse": lambda: bench_dse.run(quick=quick, smoke=smoke),
        "sweep_sharded": lambda: bench_sweep_sharded.run(
            quick=quick, smoke=smoke
        ),
        "serve": lambda: bench_serve.run(quick=quick, smoke=smoke),
        "table3": lambda: bench_table3.run(
            accelerators=small, budgets=budgets, seeds=t3_seeds
        ),
        "table4": lambda: bench_table4.run(accelerators=small, budgets=budgets),
        "fig45": lambda: bench_fig45.run(**fig_kw),
        "tpu": lambda: bench_tpu_packing.run(**tpu_kw),
        "seqpack": lambda: bench_seqpack.run(n_docs=n_docs),
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
    }
    selected = [args.only] if args.only else list(jobs)
    for name in selected:
        t0 = time.perf_counter()
        jobs[name]()
        print(f"[bench {name} done in {time.perf_counter() - t0:.1f}s]\n")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
