"""Pallas TPU kernel: fused multi-chain SA delta-cost step.

Each annealing step proposes one buffer-swap move per chain; only the
touched bins change cost.  The kernel evaluates, for every chain at once,

    d_e(chain) = sum_b cost(new_b) - cost(old_b),
    cost(w, h) = min_m ceil(w / w_m) * ceil(h / d_m)

over the BRAM aspect modes — pure integer VPU work with the per-chain
reduction fused into the same program, so one step is a single kernel
launch regardless of the chain count.

Layout: one (P, C, T) int32 block per call, its planes the touched bins'
old/new geometry: P = 4 (old_w, old_h, new_w, new_h) or, with RAM-kind
lanes, P = 6 (old_w, old_h, old_k, new_w, new_h, new_k).  The block is one
host-to-device put and one kernel operand.  T is padded to a lane multiple
and C to the sublane tile; empty slots carry w = h = 0 and contribute
nothing.  The grid tiles the chains; each program reduces a
(P, CHAIN_TILE, T) block to a (CHAIN_TILE, 1) delta column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the per-kind cost block is shared with the fitness kernel so the two
# Pallas bodies can never drift apart arithmetically
from repro.kernels.binpack_fitness.kernel import kind_cost_block
from repro.kernels.platform import resolve_interpret

CHAIN_TILE = 8  # chain rows per program (sublane tile for int32)


def _stacked_call(kernel, block: jax.Array, interpret) -> jax.Array:
    """Pad a (P, C, T) plane block once and reduce it to (C,) deltas."""
    p, c, t = block.shape
    pad_c = (-c) % CHAIN_TILE
    pad_t = (-t) % 128
    if pad_c or pad_t:
        block = jnp.pad(block, ((0, 0), (0, pad_c), (0, pad_t)))
    _, cp, tp = block.shape
    out = pl.pallas_call(
        kernel,
        grid=(cp // CHAIN_TILE,),
        in_specs=[pl.BlockSpec((p, CHAIN_TILE, tp), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((CHAIN_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(block)
    return out[:c, 0]


def _sa_step_kernel(x_ref, d_ref, *, modes):
    def bin_cost(w, h):
        best = jnp.full(w.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
        for mw, md in modes:
            c = ((w + (mw - 1)) // mw) * ((h + (md - 1)) // md)
            best = jnp.minimum(best, c)
        # empty slots (w == 0) cost nothing
        return jnp.where(w > 0, best, 0)

    delta = bin_cost(x_ref[2], x_ref[3]) - bin_cost(x_ref[0], x_ref[1])
    d_ref[...] = jnp.sum(delta, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("modes", "interpret"))
def sa_step_deltas_pallas(
    block: jax.Array,  # (4, C, T) int32: old_w, old_h, new_w, new_h
    modes: tuple[tuple[int, int], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    return _stacked_call(
        functools.partial(_sa_step_kernel, modes=modes), block, interpret
    )


def _sa_step_kinds_kernel(x_ref, d_ref, *, kind_tables):
    delta = kind_cost_block(
        x_ref[3], x_ref[4], x_ref[5], kind_tables
    ) - kind_cost_block(x_ref[0], x_ref[1], x_ref[2], kind_tables)
    d_ref[...] = jnp.sum(delta, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kind_tables", "interpret"))
def sa_step_deltas_kinds_pallas(
    block: jax.Array,  # (6, C, T) int32: old_w, old_h, old_k, new_w, new_h, new_k
    kind_tables: tuple[tuple[int, tuple[tuple[int, int], ...]], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    """Heterogeneous fused delta step: per-slot kind lanes (RAM-kind
    indices) select the mode table and unit weight (same tiling as the
    homogeneous kernel)."""
    return _stacked_call(
        functools.partial(_sa_step_kinds_kernel, kind_tables=kind_tables),
        block, interpret,
    )
