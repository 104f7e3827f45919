#!/usr/bin/env python3
"""Smoke run of the packer's main path on a TPU, checked against the plain
reference.

    python chip_smoke.py                  # one TPU chip, RN152-W1A2 on U50
    python chip_smoke.py --four-chips     # four chips: mesh sweep only
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal   # tiny, no chip

One process drives every phase through the entry points a user calls
(``pack``, ``pack_sweep``, ``pack_portfolio``, ``PackingService``) with
``backend="auto"``, and compares each result with the same call made with
``backend="python"`` and the same seed: cost, packing, iteration count and
trace must be bit-identical.  Budgets are iteration/generation counts, never
wall seconds, so every run is repeatable.  Each phase prints the backend its
engines resolved to (``pallas`` on the TPU) and whether the Pallas kernels
ran in the interpreter (never on the TPU).  The seconds printed are smoke
timings, not measurements.

``--four-chips`` runs only the sharded sweep (``mesh=make_sweep_mesh(4)``)
and the same sweep unsharded, requires them bit-identical, and requires the
sharded kernel outputs to live on four distinct devices.

``--cpu-rehearsal`` runs the same phases at a tiny size on the CPU with the
Pallas kernels forced (``backend="pallas"``, so they run interpreted).
Without it, a run that finds no TPU fails.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_FREE = dict(max_seconds=1e9, patience=10**9)  # budgets decide, never the clock


class SmokeFailure(Exception):
    pass


@dataclasses.dataclass(frozen=True)
class Sizes:
    problem: str  # the deep problem: GA, SA fleet and portfolio phases
    device: str
    ga_generations: int
    sa_chains: int
    sa_iterations: int
    sweep_names: tuple  # Table-1 accelerators, each on every sweep device
    sweep_devices: tuple
    sweep_chains: int
    sweep_iterations: int
    pf_iterations: int
    pf_generations: int
    serve_problems: tuple  # (accelerator, device) per served problem
    serve_iterations: int


_TABLE1 = (
    "CNV-W1A1", "CNV-W2A2", "Tincy-YOLO", "DoReFaNet", "ReBNet",
    "RN50-W1A2", "RN101-W1A2", "RN152-W1A2",
)
CHIP = Sizes(
    problem="RN152-W1A2", device="U50",
    ga_generations=20, sa_chains=64, sa_iterations=300,
    sweep_names=_TABLE1, sweep_devices=("ZU7EV", "U50"),
    sweep_chains=4, sweep_iterations=200,
    pf_iterations=512, pf_generations=16,
    serve_problems=(("CNV-W1A1", "U50"), ("RN50-W1A2", "ZU7EV"),
                    ("RN152-W1A2", "U50"), ("Tincy-YOLO", "U50")),
    serve_iterations=200,
)
REHEARSAL = Sizes(
    problem="CNV-W1A1", device="U50",
    ga_generations=3, sa_chains=4, sa_iterations=20,
    sweep_names=("CNV-W1A1", "CNV-W2A2"), sweep_devices=("ZU7EV", "U50"),
    sweep_chains=2, sweep_iterations=20,
    pf_iterations=64, pf_generations=4,
    serve_problems=(("CNV-W1A1", "U50"), ("CNV-W2A2", "ZU7EV"),
                    ("CNV-W1A1", "ZU7EV")),
    serve_iterations=20,
)
FOUR_CHIP_SWEEP = dict(chains=8, iterations=200)  # 16 problems x 8 = 128 rows
FOUR_CHIP_REHEARSAL = dict(chains=2, iterations=20)


class _Compiles:
    """Seconds spent compiling (or loading from the persistent cache) and the
    persistent cache's hits and misses, from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
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


def _record(res) -> tuple:
    return (int(res.cost), res.solution.state_dict(), int(res.iterations),
            [c for _, c in res.trace])


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_engine(phase: str, params: dict, backend: str, interpret: bool):
    _check(params["backend"] == backend,
           f"{phase}: resolved backend {params['backend']!r}, want {backend!r}")
    _check(params["interpret"] is interpret,
           f"{phase}: interpret={params['interpret']}, want {interpret}")


def _report(phase: str, backend, interpret, wall: float, compile_s: float,
            detail: str) -> None:
    print(f"[{phase}] backend={backend} interpret={interpret} "
          f"smoke_wall_s={wall:.2f} smoke_compile_s={compile_s:.2f} "
          f"parity=bit-identical {detail}", flush=True)


def _timed(compiles: _Compiles, fn):
    c0, t0 = compiles.seconds, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, compiles.seconds - c0


# ------------------------------------------------------------------ phases
def phase_ga(c, sz, backend, interpret, compiles):
    prob = c.get_problem(sz.problem, device=sz.device)
    kw = dict(seed=0, max_generations=sz.ga_generations, **_FREE,
              **c.hyperparams(sz.problem))
    res, wall, comp = _timed(
        compiles, lambda: c.pack(prob, "ga-nfd", backend=backend, **kw))
    ref = c.pack(prob, "ga-nfd", backend="python", **kw)
    _check_engine("ga", res.params, "pallas", interpret)
    _check(_record(res) == _record(ref), "ga: differs from backend='python'")
    _check(res.iterations == sz.ga_generations, "ga: generation budget unmet")
    _report("ga", "pallas", interpret, wall, comp,
            f"problem={prob.name} n={prob.n} n_pop={res.params['n_pop']} "
            f"generations={res.iterations} cost={res.cost}")


def phase_sa(c, sz, backend, interpret, compiles):
    prob = c.get_problem(sz.problem, device=sz.device)
    kw = dict(seed=0, n_chains=sz.sa_chains, max_iterations=sz.sa_iterations,
              **_FREE)
    res, wall, comp = _timed(
        compiles, lambda: c.pack(prob, "sa-s", backend=backend, **kw))
    ref = c.pack(prob, "sa-s", backend="python", **kw)
    _check_engine("sa", res.params, "pallas", interpret)
    _check(_record(res) == _record(ref), "sa: differs from backend='python'")
    _check(res.iterations == sz.sa_iterations * sz.sa_chains,
           "sa: iteration budget unmet")
    _report("sa", "pallas", interpret, wall, comp,
            f"problem={prob.name} chains={sz.sa_chains} "
            f"chain_steps={res.iterations} cost={res.cost}")


def _sweep_problems(c, names, devices):
    return [c.get_problem(n, device=d) for d in devices for n in names]


def phase_sweep(c, sz, backend, interpret, compiles):
    probs = _sweep_problems(c, sz.sweep_names, sz.sweep_devices)
    kw = dict(seed=0, n_chains=sz.sweep_chains,
              max_iterations=sz.sweep_iterations, **_FREE)
    sw, wall, comp = _timed(
        compiles, lambda: c.pack_sweep(probs, "sa-s", backend=backend, **kw))
    ref = c.pack_sweep(probs, "sa-s", backend="python", **kw)
    for i, (a, b) in enumerate(zip(sw.results, ref.results)):
        _check_engine(f"sweep[{i}]", a.params, "pallas", interpret)
        _check(_record(a) == _record(b),
               f"sweep: candidate {i} ({probs[i].name}) differs from "
               "backend='python'")
    _report("sweep", "pallas", interpret, wall, comp,
            f"candidates={len(probs)} groups={sw.n_groups} "
            f"chains={sz.sweep_chains} costs={sw.costs().tolist()}")


def phase_portfolio(c, sz, backend, interpret, compiles):
    prob = c.get_problem(sz.problem, device=sz.device)
    kw = dict(seed=0, max_iterations=sz.pf_iterations,
              max_generations=sz.pf_generations, **_FREE)
    res, wall, comp = _timed(
        compiles, lambda: c.pack_portfolio(prob, backend=backend, **kw))
    ref = c.pack_portfolio(prob, scheduler="serial", fused=False,
                           backend="python", **kw)
    p = res.params
    kernel_groups = {
        lab: b for lab, b in p["backends"].items()
        if not lab.endswith(":scalar")  # sa-nfd's scalar loop calls no kernel
    }
    _check(set(kernel_groups.values()) == {"pallas"},
           f"portfolio: kernel groups resolved to {p['backends']}")
    _check(p["interpret"] is interpret,
           f"portfolio: interpret={p['interpret']}, want {interpret}")
    _check(p["fused"] is True, "portfolio: fused dispatch did not engage")
    got = _record(res) + (p["barriers"], p["migrations"])
    want = _record(ref) + (ref.params["barriers"], ref.params["migrations"])
    _check(got == want, "portfolio: differs from the serial python reference")
    _report("portfolio", "pallas", interpret, wall, comp,
            f"fused={p['fused']} groups={p['backends']} "
            f"barriers={p['barriers']} migrations={p['migrations']} "
            f"cost={res.cost}")


async def _serve(svc_cls, requests, backend, hyper):
    async with svc_cls(
        "sa-s", backend=backend, max_batch=4, max_wait_ms=50.0, **hyper
    ) as svc:
        results = await asyncio.gather(
            *(svc.pack(p, seed=s, deadline_ms=d) for p, s, d in requests)
        )
        return results, svc.stats()


def phase_service(c, sz, backend, interpret, compiles):
    from repro.serve import PackingService

    probs = [c.get_problem(n, device=d) for n, d in sz.serve_problems]
    seeds = [0] * len(probs) + [1, 1]
    targets = list(probs) + [probs[0], probs[-1]]
    requests = [(p, s, None) for p, s in zip(targets, seeds)]
    requests.append((probs[-1], 1, None))  # in flight with the one before
    requests.append((probs[1], 2, 1.0))  # deadline too tight to batch
    hyper = dict(n_chains=8, max_iterations=sz.serve_iterations, **_FREE)
    (results, stats), wall, comp = _timed(
        compiles,
        lambda: asyncio.run(_serve(PackingService, requests, backend, hyper)),
    )
    for i, ((p, s, _), res) in enumerate(zip(requests, results)):
        _check_engine(f"service[{i}]", res.params, "pallas", interpret)
        ref = c.pack(p, "sa-s", seed=s, backend="python", **hyper)
        _check(_record(res) == _record(ref),
               f"service: request {i} ({p.name}, seed {s}) differs from "
               "standalone pack")
    _check(stats["coalesced"] >= 1, "service: duplicate was not coalesced")
    _check(stats["deadline_fallbacks"] >= 1,
           "service: deadline request was not rushed")
    _report("service", "pallas", interpret, wall, comp,
            f"requests={stats['requests']} solved={stats['solved']} "
            f"batches={stats['batches']} coalesced={stats['coalesced']} "
            f"deadline_fallbacks={stats['deadline_fallbacks']}")


@contextlib.contextmanager
def _output_devices(seen: set):
    """Record the devices holding every row-sharded kernel output.

    The ops modules look ``probshard.row_shard`` up when they build a sharded
    callable, so wrapping it here sees exactly the outputs of the sweep's
    own sharded kernel calls."""
    import jax

    from repro.kernels import probshard

    orig = probshard.row_shard

    def row_shard(mesh, fn, n_outputs=1):
        wrapped = orig(mesh, fn, n_outputs)

        def call(*arrays):
            out = wrapped(*arrays)
            for leaf in jax.tree.leaves(out):
                seen.update(leaf.sharding.device_set)
            return out

        return call

    probshard.row_shard = row_shard
    try:
        yield seen
    finally:
        probshard.row_shard = orig


def phase_four_chips(c, rehearsal, backend, interpret, compiles):
    import jax

    from repro.launch.mesh import make_sweep_mesh

    size = FOUR_CHIP_REHEARSAL if rehearsal else FOUR_CHIP_SWEEP
    probs = _sweep_problems(c, _TABLE1, ("ZU7EV", "U50"))
    kw = dict(seed=0, backend=backend, n_chains=size["chains"],
              max_iterations=size["iterations"], **_FREE)
    mesh = make_sweep_mesh(4)
    seen: set = set()
    with _output_devices(seen):
        shr, wall, comp = _timed(
            compiles, lambda: c.pack_sweep(probs, "sa-s", mesh=mesh, **kw))
    one, wall1, comp1 = _timed(
        compiles, lambda: c.pack_sweep(probs, "sa-s", mesh=None, **kw))
    for i, (a, b) in enumerate(zip(shr.results, one.results)):
        _check_engine(f"mesh[{i}]", a.params, "pallas", interpret)
        _check(_record(a) == _record(b),
               f"four-chips: candidate {i} ({probs[i].name}) differs "
               "between mesh=4 and mesh=None")
    ids = sorted(d.id for d in seen)
    print(f"[four-chips] sharded kernel outputs on devices {ids} "
          f"(mesh {[d.id for d in mesh.devices.flat]}, "
          f"{len(jax.devices())} visible)", flush=True)
    _check(len(seen) == 4, f"four-chips: outputs on {len(seen)} device(s)")
    rows = len(probs) * size["chains"]
    _report("four-chips", "pallas", interpret, wall, comp,
            f"candidates={len(probs)} rows={rows} rows_per_chip={rows // 4} "
            f"unsharded_wall_s={wall1:.2f} unsharded_compile_s={comp1:.2f}")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh sweep and its "
                         "unsharded comparison")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, Pallas kernels interpreted")
    args = ap.parse_args(argv)

    import jax

    import repro.core as c

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        print(f"chip_smoke: JAX platform is {platform!r}, this run needs "
              f"{want!r} (use --cpu-rehearsal with JAX_PLATFORMS=cpu to "
              "rehearse without a chip)", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    # on the chip "auto" must find the Pallas kernels by itself; the CPU
    # rehearsal forces them so the same path runs, interpreted
    backend = "pallas" if args.cpu_rehearsal else "auto"
    interpret = args.cpu_rehearsal
    print(f"chip_smoke: platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}", flush=True)

    compiles = _Compiles()
    t0 = time.perf_counter()
    try:
        with compiles.listening():
            if args.four_chips:
                phase_four_chips(c, args.cpu_rehearsal, backend, interpret,
                                 compiles)
            else:
                sz = REHEARSAL if args.cpu_rehearsal else CHIP
                for phase in (phase_ga, phase_sa, phase_sweep,
                              phase_portfolio, phase_service):
                    phase(c, sz, backend, interpret, compiles)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f}s "
          f"(smoke timing); compile_s={compiles.seconds:.2f} "
          f"persistent_cache_hits={compiles.hits} "
          f"persistent_cache_misses={compiles.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
