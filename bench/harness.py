"""Runs one cell of the benchmark: set-up, measured window, check, result.

A cell is an entry of ``BENCHMARK.json`` ``workloads``.  Everything that
belongs to it is found by name: the configuration's file (``configs``
``file``), its solver's file ``bench/solvers/<algorithm>.py`` (settings,
budget, warm-up, reference replay and control), the traffic mix
``bench/traffic/<traffic>.json`` and each per-layer metric's reader
``bench/metrics/<metric>.py``.  The mix's
``entry`` picks one of three loops, which call the program's own entry
points:

* ``pack``  - a closed loop of ``repro.core.pack`` calls;
* ``sweep`` - a closed loop of ``repro.core.pack_sweep`` calls over every
  problem of the configuration;
* ``serve`` - an open loop of ``PackingService.pack`` requests.

A closed loop issues work until ``seconds`` have passed; the window then
closes when the call in flight returns, so a rate is whole calls over the
whole window.  The open loop sends every request due in ``[0, seconds)``
and closes when the last one is answered.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

from bench import generator
from bench.reference import INVENTORY_PENALTY, ReferenceProblem, audit, canonical

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
GRACE_S = 60.0  # how long past the window an open loop waits for an answer
SOLVERS = Path(__file__).resolve().parent / "solvers"


class CellError(Exception):
    """The cell cannot be run as named; no result is printed."""


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    root: Path


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer, root)


def metric_reader(root: Path, metric: str):
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------- problems and answers
def _rows(cfg, acc):
    return [(int(n), tuple(int(x) for x in s)) for n, s in cfg["accelerators"][acc]]


def program_problems(cfg: dict) -> list:
    """The configuration's problems, built with the program's classes."""
    from repro.core.problem import (
        OCMInventory, PackingProblem, RAMKind, buffers_from_shape_rows,
    )

    kinds = {n: RAMKind(n, tuple(tuple(m) for m in k["modes"]), int(k["capacity_bits"]))
             for n, k in cfg["ram_kinds"].items()}
    out = []
    for p in cfg["problems"]:
        dev = cfg["devices"][p["device"]]
        ocm = OCMInventory(tuple(kinds[k] for k in dev["kinds"]),
                           tuple(int(c) for c in dev["counts"]), name=p["device"])
        out.append(PackingProblem(
            buffers_from_shape_rows(_rows(cfg, p["accelerator"])),
            max_items=int(cfg["max_items"]),
            name=f"{p['accelerator']}@{p['device']}", ocm=ocm,
        ))
    return out


def reference_problems(cfg: dict) -> list[ReferenceProblem]:
    out = []
    for p in cfg["problems"]:
        dev = cfg["devices"][p["device"]]
        out.append(ReferenceProblem(
            _rows(cfg, p["accelerator"]), cfg["max_items"],
            [cfg["ram_kinds"][k] for k in dev["kinds"]], dev["counts"],
            name=f"{p['accelerator']}@{p['device']}",
        ))
    return out


def solver_file(algorithm: str):
    """The module ``bench/solvers/<algorithm>.py``: ``settings(cfg)``,
    ``budget(cfg)``, ``warm(cfg)``, ``replay(ref, seed, cfg)`` and
    ``control(ref, seed, cfg)`` of one of the program's solvers."""
    path = SOLVERS / f"{algorithm}.py"
    if not path.is_file():
        raise CellError(f"no solver file for algorithm {algorithm!r} ({path.name})")
    spec = importlib.util.spec_from_file_location(f"bench_solver_{algorithm}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def solver_settings(cfg: dict) -> tuple[str, float, dict]:
    """(algorithm, wall cap, keyword arguments) of the program's solver."""
    s = cfg["solver"]
    if s["patience"] is not None or s["max_seconds"] is not None:
        raise CellError("the benchmark runs on iteration budgets alone")
    return s["algorithm"], 1e12, solver_file(s["algorithm"]).settings(cfg)


@dataclasses.dataclass
class Answer:
    problem: int
    seed: int
    bins: list
    kinds: list
    cost: int
    trace: list
    iterations: int
    backend: str
    interpret: bool


def answer_of(res, problem: int, seed: int) -> Answer:
    return Answer(
        problem=problem, seed=seed,
        bins=[list(map(int, b)) for b in res.solution.bins],
        kinds=[int(k) for k in res.solution.kinds],
        cost=int(res.cost), trace=[c for _, c in res.trace],
        iterations=int(res.iterations),
        backend=res.params.get("backend"), interpret=res.params.get("interpret"),
    )


# -------------------------------------------------------------- compiles
class Compiles:
    """Compile events and persistent-cache hits and misses, from
    ``jax.monitoring`` (copied from the program's ``chip_smoke.py``)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration

    def _on_event(self, event, **_):
        with self._lock:
            if event == _CACHE_HIT:
                self.hits += 1
            elif event == _CACHE_MISS:
                self.misses += 1

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self._on_duration)
            mon.unregister_event_listener(self._on_event)


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------ loops
@dataclasses.dataclass
class Env:
    cell: Cell
    problems: list
    algorithm: str
    max_seconds: float
    solver: dict
    backend: str
    seed: int
    traced: bool = False
    rules: object = None  # the solver's file (``solver_file``)


@dataclasses.dataclass
class Window:
    answers: list
    attempted: int
    failed: int
    elapsed_s: float
    metrics: dict
    notes: dict
    stats: dict | None = None
    requests: list | None = None  # (due, problem, seed), perf_counter seconds
    batches: list | None = None  # (start, end, {(problem, seed)}) per solve_batch


def warm_pack(env: Env) -> None:
    import repro.core as c

    override, compile_kernel = env.rules.warm(env.cell.config)
    compile_kernel(env.problems, [1], env.backend)
    for prob in env.problems:  # fills the problem's own lookup caches
        c.pack(prob, env.algorithm, seed=0, max_seconds=env.max_seconds,
               backend=env.backend, **{**env.solver, **override})


def run_pack(env: Env, seconds: float) -> Window:
    import repro.core as c

    seeds = generator.SolverSeeds(env.seed)
    answers, failed, k = [], 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        i = k % len(env.problems)
        (s,) = seeds.take(1)
        try:
            with _span("bench.solve", env.traced):
                res = c.pack(env.problems[i], env.algorithm, seed=s,
                             max_seconds=env.max_seconds, backend=env.backend,
                             **env.solver)
            answers.append(answer_of(res, i, s))
        except Exception:
            failed += 1
            traceback.print_exc(file=sys.stderr)
        k += 1
    elapsed = time.perf_counter() - t0
    solves = len(answers)
    return Window(answers, k, failed, elapsed,
                  {"solve_s": elapsed / solves if solves else None}, {"solves": solves})


def warm_sweep(env: Env) -> None:
    import repro.core as c

    override, _ = env.rules.warm(env.cell.config)
    c.pack_sweep(env.problems, env.algorithm, seeds=list(range(len(env.problems))),
                 max_seconds=env.max_seconds, backend=env.backend,
                 **{**env.solver, **override})


def run_sweep(env: Env, seconds: float) -> Window:
    import repro.core as c

    seeds = generator.SolverSeeds(env.seed)
    n = len(env.problems)
    answers, attempted, failed, sweeps = [], 0, 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ss = seeds.take(n)
        attempted += n
        try:
            with _span("bench.sweep", env.traced):
                sw = c.pack_sweep(env.problems, env.algorithm, seeds=ss,
                                  max_seconds=env.max_seconds,
                                  backend=env.backend, **env.solver)
            answers += [answer_of(r, i, s) for i, (r, s) in enumerate(zip(sw.results, ss))]
            sweeps += 1
        except Exception:
            failed += n
            traceback.print_exc(file=sys.stderr)
    elapsed = time.perf_counter() - t0
    return Window(answers, attempted, failed, elapsed,
                  {"candidates_per_s": len(answers) / elapsed}, {"sweeps": sweeps})


def warm_serve(env: Env) -> None:
    from repro.core.dse import solve_batch

    t = env.cell.traffic["service"]
    override, compile_kernel = env.rules.warm(env.cell.config)
    compile_kernel(env.problems, range(1, int(t["max_batch"]) + 1), env.backend)
    probs = env.problems
    for lo in range(0, len(probs), int(t["max_batch"])):
        part = probs[lo: lo + int(t["max_batch"])]
        solve_batch(part, env.algorithm, seeds=[0] * len(part),
                    max_seconds=env.max_seconds, backend=env.backend,
                    **{**env.solver, **override})


@contextlib.contextmanager
def _batch_spans(env: Env, batches: list):
    """Records every micro-batch the service hands to the solver as
    ``(start, end, {(problem, seed)})`` on ``perf_counter``, under a
    ``bench.batch`` span in a traced run."""
    from repro.core import dse

    orig = dse.solve_batch
    index = {id(p): i for i, p in enumerate(env.problems)}

    def solve_batch(problems, *a, seeds, **kw):
        keys = {(index[id(p)], int(s)) for p, s in zip(problems, seeds)}
        start = time.perf_counter()
        try:
            with _span("bench.batch", env.traced):
                return orig(problems, *a, seeds=seeds, **kw)
        finally:
            batches.append((start, time.perf_counter(), keys))

    dse.solve_batch = solve_batch
    try:
        yield
    finally:
        dse.solve_batch = orig


TAIL_QS = (0.5, 0.8, 0.9, 0.95)  # request latency quantiles an open loop reports


def _ms(values, q):
    return generator.nearest_rank(values, q) * 1e3 if values else None


def run_serve(env: Env, seconds: float) -> Window:
    from repro.serve import PackingService

    t = env.cell.traffic
    plan = generator.arrivals(env.seed, seconds, len(env.problems),
                              float(t["rate_hz"]), float(t["zipf_a"]),
                              float(t["revisit"]))
    store_dir = tempfile.mkdtemp(prefix="bench-store-")
    # a request that fails or is never answered keeps +inf: it misses any
    # latency limit and still counts in the rank
    latency = [math.inf] * len(plan)
    lag, answers, batches = [], [], []
    failed = 0
    t0 = None

    async def drive():
        nonlocal failed, t0
        async with PackingService(
            env.algorithm, store_dir=store_dir, backend=env.backend,
            max_seconds=env.max_seconds, **t["service"], **env.solver,
        ) as svc:
            t0 = time.perf_counter()

            async def one(i, a):
                nonlocal failed
                due = t0 + a.due_s
                delay = due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                lag.append(time.perf_counter() - due)
                try:
                    res = await svc.pack(env.problems[a.problem], seed=a.seed)
                except Exception:
                    failed += 1
                    traceback.print_exc(file=sys.stderr)
                    return
                answers.append(answer_of(res, a.problem, a.seed))
                latency[i] = time.perf_counter() - due

            # an answer may come late, up to GRACE_S past the window; one
            # that never comes counts as failed
            tasks = [asyncio.ensure_future(one(i, a)) for i, a in enumerate(plan)]
            _, late = await asyncio.wait(tasks, timeout=seconds + GRACE_S)
            for task in late:
                task.cancel()
                failed += 1
            return time.perf_counter() - t0, svc.stats()

    try:
        with _batch_spans(env, batches):
            elapsed, stats = asyncio.run(drive())
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    requests = [(t0 + a.due_s, a.problem, a.seed) for a in plan]
    tails = {f"request_p{round(100 * q)}_ms": _ms(latency, q) for q in TAIL_QS}
    # a backlog that grows through the window shows as later requests
    # waiting longer than earlier ones
    halves = ([lat for a, lat in zip(plan, latency) if a.due_s < seconds / 2],
              [lat for a, lat in zip(plan, latency) if a.due_s >= seconds / 2])
    notes = {
        "requests": len(plan), **tails,
        "p50_ms_first_half": _ms(halves[0], 0.5),
        "p50_ms_second_half": _ms(halves[1], 0.5),
        "repeats": sum(a.repeat for a in plan),
        "solved": stats["solved"], "batches": stats["batches"],
        "coalesced": stats["coalesced"], "cache_hits": stats["cache_hits_mem"],
        "batch_solve_ms_p50": _ms([e - s for s, e, _ in batches], 0.5),
        "generator_lag_max_ms": max(lag) * 1e3 if lag else None,
        "generator_lag_p95_ms": _ms(lag, 0.95),
    }
    metrics = {k: v if v is not None and math.isfinite(v) else None for k, v in tails.items()}
    return Window(answers, len(plan), failed, elapsed, metrics, notes, stats,
                  requests, batches)


LOOPS = {
    "pack": (warm_pack, run_pack),
    "sweep": (warm_sweep, run_sweep),
    "serve": (warm_serve, run_serve),
}


# ------------------------------------------------------------------ check
def check(env: Env, win: Window, on_chip: bool) -> tuple[bool, dict, dict]:
    """Every answer is audited; a sample of distinct tasks drawn from the
    seed, always with the largest problem, is replayed by the reference."""
    cfg = env.cell.config
    refs = reference_problems(cfg)
    budget = env.rules.budget(cfg)
    penalty = cfg["solver"].get("inventory_penalty", INVENTORY_PENALTY)
    audit_failures = 0
    for a in win.answers:
        found = audit(refs[a.problem], a.bins, a.kinds, a.cost, a.trace[-1],
                      a.iterations, budget, penalty)
        if found:
            audit_failures += 1
            print(f"audit: {refs[a.problem].name} seed {a.seed}: {found}", file=sys.stderr)
    off_kernel = sum(a.backend != "pallas" or a.interpret != (not on_chip)
                     for a in win.answers)

    tasks: dict = {}
    for a in win.answers:
        tasks.setdefault((a.problem, a.seed), a)
    keys = sorted(tasks)
    big = max(range(len(keys)), key=lambda j: refs[keys[j][0]].n) if keys else None
    picked = generator.sample(env.seed, len(keys),
                              int(env.cell.traffic["check_sample"]), must=big)
    mismatches = 0
    t0 = time.perf_counter()
    for j in picked:
        p, s = keys[j]
        a = tasks[(p, s)]
        want = env.rules.replay(refs[p], s, cfg)
        same = (a.cost == want["cost"]
                and canonical(a.bins, a.kinds) == canonical(want["bins"], want["kinds"])
                and [float(x) for x in a.trace] == [float(x) for x in want["trace"]]
                and a.iterations == want["iterations"])
        if not same:
            mismatches += 1
            print(f"replay: {refs[p].name} seed {s}: cost {a.cost} vs {want['cost']}, "
                  f"trace {len(a.trace)} vs {len(want['trace'])} points", file=sys.stderr)
    checks = {
        "failed": {"value": win.failed, "limit": 0},
        "audit_failures": {"value": audit_failures, "limit": 0},
        "replay_mismatches": {"value": mismatches, "limit": 0},
        "off_kernel_answers": {"value": off_kernel, "limit": 0},
    }
    correct = (bool(win.answers) and bool(picked)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    notes = {"answers": len(win.answers), "replayed": len(picked),
             "reference_s": time.perf_counter() - t0}
    return correct, checks, notes


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read."""

    trace: object
    calls: list
    peaks: dict
    stats: dict | None
    requests: list | None = None
    batches: list | None = None
    elapsed_s: float | None = None  # the window's length on the host's clock


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        allow_cpu: bool = False, backend: str = "auto") -> dict:
    """One run of ``cell``; returns the result line as a dict.  Raises
    ``CellError`` where no result may be printed."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise CellError(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < cell.chips:
        raise CellError(f"the cell asks for {cell.chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    on_chip = platform == "tpu"
    peaks = None
    if trace:
        from bench.peaks import peak

        try:
            peaks = peak(kind) if on_chip else {}
        except KeyError as e:
            raise CellError(str(e)) from None

    entry = cell.traffic["entry"]
    if entry not in LOOPS:
        raise CellError(f"unknown traffic entry {entry!r}")
    warm, drive = LOOPS[entry]
    algorithm, max_seconds, solver = solver_settings(cell.config)
    env = Env(cell, program_problems(cell.config), algorithm, max_seconds,
              solver, backend, int(seed), traced=bool(trace), rules=solver_file(algorithm))
    warm(env)
    gc.collect()

    compiles = Compiles()
    spans = profile = None
    tmp = None
    if trace:
        from bench.tracing import DispatchSpans, Profile

        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        spans, profile = DispatchSpans(), Profile(tmp)
        spans.install()
        profile.start()
    try:
        with compiles.listening():
            t_window = time.time()
            with _span("bench.window", bool(trace)):
                win = drive(env, float(seconds))
    finally:
        trace_path = profile.stop() if profile is not None else None
        if spans is not None:
            spans.remove()
    setup_s = t_window - t_start

    memory = 0
    for d in devices[: cell.chips]:
        stats = d.memory_stats() or {}
        memory = max(memory, int(stats.get("peak_bytes_in_use", 0)))
    env.problems = None
    gc.collect()
    correct, checks, check_notes = check(env, win, on_chip)

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    out_metrics: dict = {}
    breakdown = summary = None
    if trace:
        from bench.tracing import reduce_trace

        summary = reduce_trace(trace_path)
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    view = RunView(summary, spans.calls if spans is not None else [], peaks, win.stats,
                   win.requests, win.batches, win.elapsed_s)
    # an untraced run prints, as notes, the per-layer metrics it can read
    # without a trace
    layer = {m["name"]: metric_reader(cell.root, m["name"])(view) for m in cell.per_layer}
    if trace:
        for m in cell.per_layer:
            if layer[m["name"]] is not None:
                out_metrics[m["name"]] = {"value": layer[m["name"]], "unit": m["unit"]}
        ops = sorted(summary.op_seconds.items(), key=lambda x: -x[1])[:10]
        breakdown = {"device_ops": [[n, s] for n, s in ops],
                     "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}
        print(f"trace: kernel events {summary.kernel_events}, "
              f"dispatch calls {len(spans.calls)}", file=sys.stderr)
    else:
        values = dict(win.metrics, setup_s=setup_s)
        for m in cell.end_to_end:
            if values.get(m["name"]) is None:
                if not correct:  # a run gone wrong still prints its line
                    continue
                raise CellError(f"the {entry!r} loop gives no {m['name']!r}")
            out_metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        win.notes.update((k, v) for k, v in layer.items() if v is not None)

    for k, v in {"window_s": win.elapsed_s, **win.notes, **check_notes}.items():
        print(f"note: {k}={v}", file=sys.stderr)
    print(f"note: compiles_in_window={compiles.count} compile_s={compiles.seconds} "
          f"cache_hits={compiles.hits} cache_misses={compiles.misses}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check: {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    line = {"correct": correct, "attempted": win.attempted, "failed": win.failed,
            "metrics": out_metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
