"""Pallas TPU kernel: population-parallel bin-packing fitness evaluation.

The GA's compute hot-spot is evaluating the BRAM cost of every individual
every generation:  cost(bin) = min_m ceil(w / w_m) * ceil(h / d_m)  over the
BRAM aspect modes.  Pure integer VPU work, embarrassingly parallel over
(population x bins) — ideal for lane-parallel evaluation.

Layout: widths/heights are (P, NB) int32, NB padded to a lane multiple;
empty bins carry w = h = 0 and cost 0.  The grid tiles the population; each
program evaluates a (POP_TILE, NB) block entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

POP_TILE = 8  # population rows per program (sublane tile for int32)


def _fitness_kernel(w_ref, h_ref, cost_ref, *, modes):
    w = w_ref[...]
    h = h_ref[...]
    best = jnp.full(w.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
    for mw, md in modes:
        c = ((w + (mw - 1)) // mw) * ((h + (md - 1)) // md)
        best = jnp.minimum(best, c)
    # empty slots (w == 0) cost nothing
    cost_ref[...] = jnp.where(w > 0, best, 0)


def kind_cost_block(w, h, k, kind_tables):
    """Per-kind lane-selected bin cost, shared by every heterogeneous kernel
    body (fitness and SA delta): the kind count is tiny (2-4), so the
    per-kind cost planes are computed unconditionally and lane-selected —
    pure VPU work, no gather.  Empty slots (w == 0) cost nothing."""
    out = jnp.zeros(w.shape, jnp.int32)
    for ki, (weight, modes) in enumerate(kind_tables):
        best = jnp.full(w.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
        for mw, md in modes:
            c = ((w + (mw - 1)) // mw) * ((h + (md - 1)) // md)
            best = jnp.minimum(best, c)
        out = jnp.where(k == ki, best * jnp.int32(weight), out)
    return jnp.where(w > 0, out, 0)


def _fitness_kinds_kernel(w_ref, h_ref, k_ref, cost_ref, *, kind_tables):
    """Heterogeneous variant: a kind-index plane selects, per bin, which
    static mode table and unit weight apply."""
    cost_ref[...] = kind_cost_block(
        w_ref[...], h_ref[...], k_ref[...], kind_tables
    )


@functools.partial(jax.jit, static_argnames=("modes", "interpret"))
def binpack_fitness_pallas(
    widths: jax.Array,  # (P, NB) int32
    heights: jax.Array,  # (P, NB) int32
    modes: tuple[tuple[int, int], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    p, nb = widths.shape
    pad_p = (-p) % POP_TILE
    pad_b = (-nb) % 128
    if pad_p or pad_b:
        widths = jnp.pad(widths, ((0, pad_p), (0, pad_b)))
        heights = jnp.pad(heights, ((0, pad_p), (0, pad_b)))
    pp, nbp = widths.shape
    out = pl.pallas_call(
        functools.partial(_fitness_kernel, modes=modes),
        grid=(pp // POP_TILE,),
        in_specs=[
            pl.BlockSpec((POP_TILE, nbp), lambda i: (i, 0)),
            pl.BlockSpec((POP_TILE, nbp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((POP_TILE, nbp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp, nbp), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(widths, heights)
    return out[:p, :nb]


@functools.partial(jax.jit, static_argnames=("kind_tables", "interpret"))
def binpack_fitness_kinds_pallas(
    widths: jax.Array,  # (P, NB) int32
    heights: jax.Array,  # (P, NB) int32
    kinds: jax.Array,  # (P, NB) int32 RAM-kind indices
    kind_tables: tuple[tuple[int, tuple[tuple[int, int], ...]], ...],
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    p, nb = widths.shape
    pad_p = (-p) % POP_TILE
    pad_b = (-nb) % 128
    if pad_p or pad_b:
        pad = ((0, pad_p), (0, pad_b))
        widths = jnp.pad(widths, pad)
        heights = jnp.pad(heights, pad)
        kinds = jnp.pad(kinds, pad)  # kind 0 on w == 0 slots costs nothing
    pp, nbp = widths.shape
    out = pl.pallas_call(
        functools.partial(_fitness_kinds_kernel, kind_tables=kind_tables),
        grid=(pp // POP_TILE,),
        in_specs=[pl.BlockSpec((POP_TILE, nbp), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((POP_TILE, nbp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp, nbp), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(widths, heights, kinds)
    return out[:p, :nb]
