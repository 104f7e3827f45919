"""Pallas TPU kernel: fused multi-chain SA delta-cost step.

Each annealing step proposes one buffer-swap move per chain; only the
touched bins change cost.  The kernel evaluates, for every chain at once,

    d_e(chain) = sum_b cost(new_b) - cost(old_b),
    cost(w, h) = min_m ceil(w / w_m) * ceil(h / d_m)

over the BRAM aspect modes — pure integer VPU work with the per-chain
reduction fused into the same program, so one step is a single kernel
launch regardless of the chain count.

Layout: four (C, T) int32 matrices (old/new width/height of the touched
bins), T padded to a lane multiple and C to the sublane tile; empty slots
carry w = h = 0 and contribute nothing.  The grid tiles the chains; each
program reduces a (CHAIN_TILE, T) block to a (CHAIN_TILE, 1) delta column.
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


def _sa_step_kernel(ow_ref, oh_ref, nw_ref, nh_ref, d_ref, *, modes):
    def bin_cost(w, h):
        best = jnp.full(w.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
        for mw, md in modes:
            c = ((w + (mw - 1)) // mw) * ((h + (md - 1)) // md)
            best = jnp.minimum(best, c)
        # empty slots (w == 0) cost nothing
        return jnp.where(w > 0, best, 0)

    delta = bin_cost(nw_ref[...], nh_ref[...]) - bin_cost(ow_ref[...], oh_ref[...])
    d_ref[...] = jnp.sum(delta, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("modes", "interpret"))
def sa_step_deltas_pallas(
    old_w: jax.Array,  # (C, T) int32
    old_h: jax.Array,
    new_w: jax.Array,
    new_h: jax.Array,
    modes: tuple[tuple[int, int], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    c, t = old_w.shape
    pad_c = (-c) % CHAIN_TILE
    pad_t = (-t) % 128
    if pad_c or pad_t:
        pad = ((0, pad_c), (0, pad_t))
        old_w, old_h, new_w, new_h = (
            jnp.pad(x, pad) for x in (old_w, old_h, new_w, new_h)
        )
    cp, tp = old_w.shape
    out = pl.pallas_call(
        functools.partial(_sa_step_kernel, modes=modes),
        grid=(cp // CHAIN_TILE,),
        in_specs=[pl.BlockSpec((CHAIN_TILE, tp), lambda i: (i, 0))] * 4,
        out_specs=pl.BlockSpec((CHAIN_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(old_w, old_h, new_w, new_h)
    return out[:c, 0]


def _sa_step_kinds_kernel(
    ow_ref, oh_ref, ok_ref, nw_ref, nh_ref, nk_ref, d_ref, *, kind_tables
):
    delta = kind_cost_block(
        nw_ref[...], nh_ref[...], nk_ref[...], kind_tables
    ) - kind_cost_block(ow_ref[...], oh_ref[...], ok_ref[...], kind_tables)
    d_ref[...] = jnp.sum(delta, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kind_tables", "interpret"))
def sa_step_deltas_kinds_pallas(
    old_w: jax.Array,  # (C, T) int32
    old_h: jax.Array,
    old_k: jax.Array,  # (C, T) int32 RAM-kind indices
    new_w: jax.Array,
    new_h: jax.Array,
    new_k: jax.Array,
    kind_tables: tuple[tuple[int, tuple[tuple[int, int], ...]], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    """Heterogeneous fused delta step: per-slot kind lanes select the mode
    table and unit weight (same tiling as the homogeneous kernel)."""
    c, t = old_w.shape
    pad_c = (-c) % CHAIN_TILE
    pad_t = (-t) % 128
    args = (old_w, old_h, old_k, new_w, new_h, new_k)
    if pad_c or pad_t:
        pad = ((0, pad_c), (0, pad_t))
        args = tuple(jnp.pad(x, pad) for x in args)
    cp, tp = args[0].shape
    out = pl.pallas_call(
        functools.partial(_sa_step_kinds_kernel, kind_tables=kind_tables),
        grid=(cp // CHAIN_TILE,),
        in_specs=[pl.BlockSpec((CHAIN_TILE, tp), lambda i: (i, 0))] * 6,
        out_specs=pl.BlockSpec((CHAIN_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(*args)
    return out[:c, 0]
