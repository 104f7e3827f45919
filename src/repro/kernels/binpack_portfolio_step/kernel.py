"""Pallas variant of the fused portfolio step.

Composes the two existing Pallas kernels (``binpack_fitness``'s population
evaluator and ``binpack_sa_step``'s delta-cost step) under one jit, so a TPU
run launches ONE compiled program per fused barrier segment; off-TPU the
interpreter path validates the exact same composition (``interpret=None``
decides from the platform, ``kernels.platform``).  Both kernels are
exact-integer, so the fused results stay bit-identical to the separate
dispatches (pinned in ``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.binpack_fitness.kernel import (
    binpack_fitness_kinds_pallas,
    binpack_fitness_pallas,
)
from repro.kernels.binpack_sa_step.kernel import (
    sa_step_deltas_kinds_pallas,
    sa_step_deltas_pallas,
)


@functools.partial(jax.jit, static_argnames=("modes", "interpret"))
def portfolio_step_pallas(
    W, H, old_w, old_h, new_w, new_h, modes, interpret=None
):
    nb = W.shape[-1]
    per_bin = binpack_fitness_pallas(
        W.reshape(-1, nb), H.reshape(-1, nb), modes, interpret
    )
    totals = jnp.sum(per_bin, axis=1).reshape(W.shape[:-1])
    deltas = sa_step_deltas_pallas(
        jnp.stack([old_w, old_h, new_w, new_h]), modes, interpret
    )
    return totals, deltas


@functools.partial(jax.jit, static_argnames=("kind_tables", "interpret"))
def portfolio_step_kinds_pallas(
    W, H, Km, old_w, old_h, old_k, new_w, new_h, new_k, kind_tables,
    interpret=None,
):
    nb = W.shape[-1]
    per_bin = binpack_fitness_kinds_pallas(
        W.reshape(-1, nb), H.reshape(-1, nb), Km.reshape(-1, nb),
        kind_tables, interpret,
    )
    totals = jnp.sum(per_bin, axis=1).reshape(W.shape[:-1])
    deltas = sa_step_deltas_kinds_pallas(
        jnp.stack([old_w, old_h, old_k, new_w, new_h, new_k]),
        kind_tables, interpret,
    )
    return totals, deltas
