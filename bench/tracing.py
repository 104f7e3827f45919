"""Harness spans, the profiler session, and the reduction from a device
trace to the per-layer numbers.

Spans exist only in ``--trace 1`` runs.  They are the harness's own
``jax.profiler.TraceAnnotation`` scopes, written into the profiler's trace
on the host's clock: ``bench.window`` around the measured window,
``bench.solve`` / ``bench.sweep`` / ``bench.batch`` around each call into
the program's entry points, and ``bench.dispatch.<kernel>`` around each call
into the kernel-dispatch layer (``repro/kernels/*/ops.py``):
``bench.dispatch.sa_step`` from the host's arrays in to the host's arrays
out, ``bench.dispatch.fitness`` from the device arrays the GA puts to the
result ready on the device (the GA makes the puts before the call and reads
the result back after it).

The reduction reads the trace's ``.xplane.pb`` with
``jax.profiler.ProfileData``: on each ``/device:TPU:<n>`` plane the
``XLA Ops`` line holds one event per device operation, named by its HLO
text (``%name.N = type op(...)``); a Pallas kernel is a ``custom-call``
named after its jitted entry point.  Busy time is the union of operation
intervals inside the window, averaged over the chips; an idle gap is named
by the innermost harness span that holds its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import inspect
import os
import re

WINDOW = "bench.window"
DISPATCH = "bench.dispatch."

# kernel -> the jitted entry points its Pallas custom-calls are named after
KERNEL_OPS = {
    "binpack_sa_step": ("sa_step_deltas_pallas", "sa_step_deltas_kinds_pallas"),
    "binpack_fitness": ("binpack_fitness_pallas", "binpack_fitness_kinds_pallas"),
}


# ------------------------------------------------------------------ spans
class DispatchSpans:
    """Spans around the kernel-dispatch calls, with each call's shape.

    ``install`` replaces ``sa_step_deltas`` and ``population_costs`` in
    their ops modules; the engines look them up there at call time, so every
    step and generation of the window passes through a span.  ``remove``
    puts the originals back."""

    def __init__(self):
        self.calls: list[tuple[str, int, int, list]] = []
        self._undo: list[tuple] = []

    def install(self) -> None:
        import importlib

        import jax
        import numpy as np

        calls = self.calls

        def kind_modes(a):
            kt = a["kind_tables"]
            return [list(m) for _, m in kt] if kt is not None else [list(a["modes"])]

        step_mod = importlib.import_module("repro.kernels.binpack_sa_step.ops")
        step = step_mod.sa_step_deltas
        step_sig = inspect.signature(step)

        def sa_step_deltas(*args, **kwargs):
            if np.ndim(args[0] if args else kwargs["old_w"]) != 2:
                return step(*args, **kwargs)  # reshapes, then calls back in
            with jax.profiler.TraceAnnotation(DISPATCH + "sa_step"):
                out = step(*args, **kwargs)
            bound = step_sig.bind(*args, **kwargs)
            bound.apply_defaults()
            a = bound.arguments
            rows, touched = np.shape(a["old_w"])
            calls.append(("binpack_sa_step", int(rows), int(touched), kind_modes(a)))
            return out

        fit_mod = importlib.import_module("repro.kernels.binpack_fitness.ops")
        fit = fit_mod.population_costs
        fit_sig = inspect.signature(fit)

        def population_costs(*args, **kwargs):
            if np.ndim(args[0] if args else kwargs["widths"]) != 2:
                return fit(*args, **kwargs)  # reshapes, then calls back in
            with jax.profiler.TraceAnnotation(DISPATCH + "fitness"):
                out = jax.block_until_ready(fit(*args, **kwargs))
            bound = fit_sig.bind(*args, **kwargs)
            bound.apply_defaults()
            a = bound.arguments
            population, bins = np.shape(a["widths"])
            calls.append(("binpack_fitness", int(population), int(bins), kind_modes(a)))
            return out

        for mod, name, orig, new in (
                (step_mod, "sa_step_deltas", step, sa_step_deltas),
                (fit_mod, "population_costs", fit, population_costs)):
            setattr(mod, name, new)
            self._undo.append((mod, name, orig))

    def remove(self) -> None:
        while self._undo:
            mod, name, orig = self._undo.pop()
            setattr(mod, name, orig)


class Profile:
    """One profiler session writing under ``log_dir``; no Python tracer."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file, found {found}")
        return found[0]


# -------------------------------------------------------------- reduction
@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # union of device-op intervals, averaged over chips
    n_chips: int
    op_seconds: dict  # short op name -> device seconds, summed over chips
    kernel_seconds: dict  # kernel -> device seconds, summed over chips
    kernel_events: dict  # kernel -> number of device events
    spans: list  # (name, start_ns, end_ns) harness spans inside the window
    gaps: list  # (label, seconds) idle gaps, longest first

    def span_seconds(self, prefix: str) -> list[float]:
        return [(e - s) / 1e9 for n, s, e in self.spans if n.startswith(prefix)]


def short_op_name(hlo_text: str) -> str:
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_trace(path: str, window_span: str = WINDOW) -> TraceSummary:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in {path}")
    ws, we = windows[0]
    spans = [(n, max(s, ws), min(e, we)) for n, s, e in spans
             if n != window_span and e > ws and s < we]

    kernel_of = {op: k for k, ops in KERNEL_OPS.items() for op in ops}
    op_seconds: dict = {}
    kernel_seconds = {k: 0.0 for k in KERNEL_OPS}
    kernel_events = {k: 0 for k in KERNEL_OPS}
    busy_total = 0.0
    busy_union: list = []
    chips = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    for plane in chips:
        intervals = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= ws or s >= we:
                    continue
                s, e = max(s, ws), min(e, we)
                intervals.append((s, e))
                name = short_op_name(ev.name)
                op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
                k = kernel_of.get(name)
                if k is not None and "custom-call(" in ev.name:
                    kernel_seconds[k] += (e - s) / 1e9
                    kernel_events[k] += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        busy_union = _union(busy_union + merged)
    n_chips = len(chips)
    busy_s = busy_total / 1e9 / n_chips if n_chips else 0.0

    # idle gaps: where no chip runs an operation, named by the host span
    gaps = []
    cursor = ws
    for s, e in busy_union + [(we, we)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    labelled = []
    for s, e in gaps:
        mid = (s + e) / 2
        holders = [(ee - ss, n) for n, ss, ee in spans if ss <= mid < ee]
        labelled.append((min(holders)[1] if holders else "outside harness spans",
                         (e - s) / 1e9))
    labelled.sort(key=lambda x: -x[1])
    return TraceSummary(
        window_s=(we - ws) / 1e9, busy_s=busy_s, n_chips=n_chips,
        op_seconds=op_seconds, kernel_seconds=kernel_seconds,
        kernel_events=kernel_events, spans=spans, gaps=labelled,
    )


# ------------------------------------------- arithmetic the readers share
def host_share_pct(t: TraceSummary) -> float | None:
    """Share of the window outside the dispatch spans."""
    if t is None or t.window_s <= 0:
        return None
    inside = sum(e - s for s, e in _union(
        (s, e) for n, s, e in t.spans if n.startswith(DISPATCH))) / 1e9
    return 100.0 * (t.window_s - inside) / t.window_s


def dispatch_us_per_call(t: TraceSummary) -> float | None:
    d = t.span_seconds(DISPATCH) if t is not None else []
    return 1e6 * sum(d) / len(d) if d else None


def lane_busy_pct(batches, elapsed_s: float | None) -> float | None:
    """Share of the window in which the service's lane solves a batch: the
    union of the recorded ``(start, end, keys)`` batches over its length."""
    if not batches or not elapsed_s:
        return None
    return 100.0 * sum(e - s for s, e in _union((s, e) for s, e, _ in batches)) / elapsed_s


def queue_waits(requests, batches) -> list[float]:
    """Seconds from each request's due time to the start of the batch that
    solved it.  A request is paired with the first batch that starts at or
    after its due time and holds its (problem, seed); one whose task was in
    a batch already started, or answered, is a hit and has no wait."""
    starts: dict = {}
    for start, _, keys in batches:
        for key in keys:
            starts.setdefault(key, []).append(start)
    waits = []
    for due, problem, seed in requests:
        later = [s for s in starts.get((problem, seed), ()) if s >= due]
        if later:
            waits.append(min(later) - due)
    return waits


def device_idle_pct(t: TraceSummary) -> float | None:
    if t is None or t.n_chips == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def hbm_roofline_pct(t: TraceSummary, calls, kernel: str, peaks: dict) -> float | None:
    """Least time the kernel's bytes need at the HBM peak, over its device
    time.  Integer VPU work has no published peak, so the HBM bound is the
    only one taken."""
    from bench import opcount

    if t is None or not t.kernel_events.get(kernel):
        return None
    count = {"binpack_sa_step": opcount.sa_step, "binpack_fitness": opcount.fitness}[kernel]
    nbytes = sum(count(rows, touched, km)[1] for k, rows, touched, km in calls
                 if k == kernel)
    if nbytes == 0:
        return None
    return 100.0 * (nbytes / peaks["hbm_bytes_per_s"]) / t.kernel_seconds[kernel]
