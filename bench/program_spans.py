#!/usr/bin/env python3
"""The program's own spans in a trace, and a run of a cell with them on.

The program marks its host work with ``repro.*`` spans (``repro.spans``,
off unless ``repro.spans.enable(True)``): ``repro.sa.start`` /
``repro.sa.finish`` around a fleet's encode and decode, ``repro.sa.seed``
around its NFD chain seeding with ``repro.nfd.kinds`` around each seed's
greedy RAM-kind assignment, ``repro.sa.propose`` / ``repro.sa.accept``
around each step before and after its kernel call, and
``repro.dispatch.h2d`` / ``.launch`` / ``.d2h`` inside each kernel call.
They land on the profiler's host plane beside the harness's ``bench.*``
spans, on the device's clock.

``reduce_program`` reads them from a trace's ``.xplane.pb``, clipped to the
``bench.window`` span as ``bench.tracing.reduce_trace`` clips the harness's
spans, and names each idle gap of the device by the innermost span of
either kind that holds its midpoint.  ``program_metrics`` gives the eight
numbers the spans are for.

Run as a script, it runs one cell like ``bench/run.py`` with program spans
on, and adds those numbers, the span counts and the relabelled idle gaps to
the result line (``--spans 0`` leaves them off, to time what they cost):

    python3 -m bench.program_spans --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--spans <0|1>] [--keep <trace copy>]
"""
from __future__ import annotations

import dataclasses
import time

T_START = time.time()  # set-up is counted from here, as in bench/run.py

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "repro."
WINDOW = "bench.window"

# metric -> (how it is read, span)
METRICS = {
    "seed_share_pct": ("share", "repro.sa.seed"),
    "kind_assign_share_pct": ("share", "repro.nfd.kinds"),
    "propose_us_per_step": ("mean", "repro.sa.propose"),
    "accept_us_per_step": ("mean", "repro.sa.accept"),
    "h2d_us_per_call": ("mean", "repro.dispatch.h2d"),
    "launch_us_per_call": ("mean", "repro.dispatch.launch"),
    "d2h_us_per_call": ("mean", "repro.dispatch.d2h"),
    "unspanned_pct": ("unspanned", None),
}


@dataclasses.dataclass
class ProgramTrace:
    window: tuple  # (start_ns, end_ns) of the window span
    spans: list  # (name, start_ns, end_ns) repro.* spans inside the window
    harness: list  # (name, start_ns, end_ns) bench.* spans inside the window
    gaps: list  # (label, seconds) device idle gaps, longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def count(self, name: str) -> int:
        return sum(n == name for n, _, _ in self.spans)


def reduce_program(path: str, window_span: str = WINDOW) -> ProgramTrace:
    import jax

    from bench.tracing import _union

    pd = jax.profiler.ProfileData.from_file(path)
    found = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((PREFIX, "bench.")):
                        found.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in found if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in {path}")
    ws, we = windows[0]
    inside = [(n, max(s, ws), min(e, we)) for n, s, e in found
              if n != window_span and e > ws and s < we]

    busy = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy += [(max(ev.start_ns, ws), min(ev.start_ns + ev.duration_ns, we))
                             for ev in line.events
                             if ev.start_ns + ev.duration_ns > ws and ev.start_ns < we]
    gaps, cursor = [], ws
    for s, e in _union(busy) + [(we, we)]:
        if s > cursor:
            mid = (cursor + s) / 2
            holders = [(ee - ss, n) for n, ss, ee in inside if ss <= mid < ee]
            gaps.append((min(holders)[1] if holders else "outside harness spans",
                         (s - cursor) / 1e9))
        cursor = max(cursor, e)
    gaps.sort(key=lambda x: -x[1])
    return ProgramTrace(
        window=(ws, we),
        spans=[x for x in inside if x[0].startswith(PREFIX)],
        harness=[x for x in inside if not x[0].startswith(PREFIX)],
        gaps=gaps,
    )


def program_share_pct(t: ProgramTrace, name: str) -> float | None:
    """Share of the window inside ``name`` spans (their union)."""
    from bench.tracing import _union

    iv = [(s, e) for n, s, e in t.spans if n == name]
    if not iv or t.window_s <= 0:
        return None
    return 100.0 * sum(e - s for s, e in _union(iv)) / 1e9 / t.window_s


def program_us_per_span(t: ProgramTrace, name: str) -> float | None:
    """Mean duration of one ``name`` span."""
    d = [e - s for n, s, e in t.spans if n == name]
    return sum(d) / len(d) / 1e3 if d else None


def unspanned_pct(t: ProgramTrace) -> float | None:
    """Share of the window covered by no program span."""
    from bench.tracing import _union

    if not t.spans or t.window_s <= 0:
        return None
    covered = sum(e - s for s, e in _union((s, e) for _, s, e in t.spans)) / 1e9
    return 100.0 * (t.window_s - covered) / t.window_s


def program_metrics(t: ProgramTrace) -> dict:
    read = {"share": program_share_pct, "mean": program_us_per_span}
    return {m: unspanned_pct(t) if how == "unspanned" else read[how](t, name)
            for m, (how, name) in METRICS.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", help="copy the trace's .xplane.pb here")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness, tracing
    from repro import spans

    kept = {}
    reduce_trace = tracing.reduce_trace

    def reduce_both(path, *a, **kw):  # the harness imports it at call time
        kept["program"] = reduce_program(path)
        if args.keep:
            shutil.copy(path, args.keep)
        return reduce_trace(path, *a, **kw)

    tracing.reduce_trace = reduce_both
    spans.enable(bool(args.spans))
    try:
        cell = harness.load_cell(ROOT, args.workload)
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except (harness.CellError, ImportError, FileNotFoundError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    finally:
        spans.enable(False)
        tracing.reduce_trace = reduce_trace
    t = kept.get("program")
    if t is not None:
        calls = [(e - s) / 1e9 for n, s, e in t.harness
                 if n in ("bench.solve", "bench.sweep")]
        counts: dict = {}
        for n, _, _ in t.spans:
            counts[n] = counts.get(n, 0) + 1
        line["program"] = {
            "metrics": program_metrics(t),
            "span_counts": counts,
            "call_s_mean": sum(calls) / len(calls) if calls else None,
            "idle_gaps": [[n, s] for n, s in t.gaps[:10]],
        }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
