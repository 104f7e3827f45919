#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (``python3 -m bench.run`` works the same).
The cell is an entry of ``BENCHMARK.json`` ``workloads``.  The run sets up
(imports, compile cache, problems, warm-up of every kernel shape the cell
uses), measures for ``--seconds``, checks every answer of the window against
the plain reference in ``bench/reference.py``, and prints one JSON object as
the last line of standard output.  ``--trace 1`` runs the window under the
profiler and reports the per-layer metrics instead of the end-to-end ones.
A run that finds no TPU, or fewer chips than the cell asks for, exits with
code 2 and prints no result.
"""
import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)  # run as a script: import bench's modules as a package
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness

    try:
        cell = harness.load_cell(ROOT, args.workload)
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except (harness.CellError, ImportError, FileNotFoundError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
