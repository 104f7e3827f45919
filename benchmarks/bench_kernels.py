"""Kernel micro-benchmarks: pallas vs jnp ref, us/call.

The Pallas kernels compile on a TPU and run in the interpreter elsewhere
(``kernels.platform``); off-TPU the numbers only demonstrate the harness.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.problem import BRAM18_MODES
from repro.kernels.binpack_fitness.kernel import binpack_fitness_pallas
from repro.kernels.binpack_fitness.ref import binpack_fitness_ref
from repro.kernels.packed_gather.kernel import packed_gather_matvec
from repro.kernels.packed_gather.ref import packed_gather_ref

from .common import emit


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rng = np.random.default_rng(0)
    rows = []
    for p, nb in [(50, 1000), (75, 2500)]:
        w = jnp.asarray(rng.integers(1, 80, (p, nb)), jnp.int32)
        h = jnp.asarray(rng.integers(1, 70_000, (p, nb)), jnp.int32)
        us_pl = _time(lambda a, b: binpack_fitness_pallas(a, b, BRAM18_MODES), w, h)
        us_ref = _time(jax.jit(lambda a, b: binpack_fitness_ref(a, b, BRAM18_MODES)), w, h)
        rows.append([f"binpack_fitness_{p}x{nb}", round(us_pl, 1), round(us_ref, 1)])
    for r, c, n in [(512, 512, 4), (2048, 1024, 4)]:
        bank = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        seg = jnp.asarray(rng.integers(0, n, r), jnp.int32)
        us_pl = _time(lambda b, xx, s: packed_gather_matvec(b, xx, s), bank, x, seg)
        us_ref = _time(jax.jit(packed_gather_ref), bank, x, seg)
        rows.append([f"packed_gather_{r}x{c}x{n}", round(us_pl, 1), round(us_ref, 1)])
    emit("kernels_microbench", ["name", "pallas_us", "jnp_ref_us"], rows)
    return rows
