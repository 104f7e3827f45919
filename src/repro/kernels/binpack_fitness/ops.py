"""Jit'd wrapper: per-individual total RAM cost for a padded population.

This is the GA's generation-evaluation primitive: rows are individuals,
columns are bins, entries are the bin geometry; empty (padded) slots carry
``width == 0`` and cost nothing.  ``backend="auto"`` (the default) picks the
Pallas kernel when a TPU is attached and the pure-jnp reference otherwise.
The Pallas kernel compiles on a TPU and is interpreted elsewhere
(``kernels.platform``).

Heterogeneous OCM problems pass a parallel ``kinds`` matrix plus the
problem's precomputed ``kind_tables`` (``((weight, modes), ...)`` per RAM
kind); the homogeneous call signature and its jit cache are untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.problem import BRAM18_MODES
from repro.kernels.platform import on_tpu

from .kernel import binpack_fitness_kinds_pallas, binpack_fitness_pallas
from .ref import binpack_fitness_kinds_ref, binpack_fitness_ref


@functools.partial(jax.jit, static_argnames=("modes",))
def _ref_totals(widths, heights, modes):
    return jnp.sum(binpack_fitness_ref(widths, heights, modes), axis=1)


@functools.partial(jax.jit, static_argnames=("kind_tables",))
def _ref_totals_kinds(widths, heights, kinds, kind_tables):
    return jnp.sum(
        binpack_fitness_kinds_ref(widths, heights, kinds, kind_tables), axis=1
    )


def population_costs(
    widths,
    heights,
    modes=BRAM18_MODES,
    backend: str = "auto",
    kinds=None,
    kind_tables=None,
    mesh=None,
):
    """(P, NB) geometry -> (P,) total cost per individual.

    ``kinds`` (a (P, NB) int matrix of RAM-kind indices) together with
    ``kind_tables`` routes evaluation through per-kind mode tables; without
    them the single mode set ``modes`` applies to every bin.

    A leading *problem axis* is also accepted on every backend:
    ``(NP, P, NB)`` inputs return ``(NP, P)`` totals, evaluating a whole
    fleet of padded problems in one call (the DSE sweep path —
    docs/DESIGN.md section 10).  Padded lanes are masked by the zero-width
    convention: a padded bin slot (or an entirely padded problem row) has
    width 0 and costs nothing.

    ``mesh`` (a 1-D ``("prob",)`` mesh from ``launch.mesh.make_sweep_mesh``)
    row-shards the evaluation across devices via ``shard_map``: the leading
    axis is zero-padded to a multiple of the mesh size, each device costs
    its contiguous row block, and results are bit-identical to the
    unsharded call (exact integer arithmetic — docs/DESIGN.md section 14).
    """
    widths = jnp.asarray(widths)
    heights = jnp.asarray(heights)
    if widths.ndim == 3:
        np_, p_, nb_ = widths.shape
        totals = population_costs(
            widths.reshape(np_ * p_, nb_),
            heights.reshape(np_ * p_, nb_),
            modes=modes,
            backend=backend,
            kinds=None if kinds is None else jnp.asarray(kinds).reshape(np_ * p_, nb_),
            kind_tables=kind_tables,
            mesh=mesh,
        )
        return totals.reshape(np_, p_)
    if backend == "auto":
        backend = "pallas" if on_tpu() else "ref"
    if mesh is not None:
        return _population_costs_sharded(
            widths, heights, modes, backend, kinds, kind_tables, mesh,
        )
    if kinds is not None:
        if kind_tables is None:
            raise ValueError("kinds requires kind_tables")
        kind_tables = tuple((int(w), tuple(m)) for w, m in kind_tables)
        if backend == "pallas":
            per_bin = binpack_fitness_kinds_pallas(
                widths, heights, kinds, kind_tables
            )
            return jnp.sum(per_bin, axis=1)
        if backend != "ref":
            raise ValueError(
                f"unknown backend {backend!r}; options: auto, pallas, ref"
            )
        return _ref_totals_kinds(widths, heights, kinds, kind_tables)
    if backend == "pallas":
        per_bin = binpack_fitness_pallas(widths, heights, tuple(modes))
        return jnp.sum(per_bin, axis=1)
    if backend != "ref":
        raise ValueError(f"unknown backend {backend!r}; options: auto, pallas, ref")
    return _ref_totals(widths, heights, tuple(modes))


_SHARD_CACHE: dict = {}


def _population_costs_sharded(
    widths, heights, modes, backend, kinds, kind_tables, mesh
):
    """Row-sharded evaluation over the ``("prob",)`` mesh (PR 8)."""
    from repro.kernels.probshard import mesh_size, pad_rows, row_shard

    k = mesh_size(mesh)
    hetero = kinds is not None
    if hetero:
        if kind_tables is None:
            raise ValueError("kinds requires kind_tables")
        kind_tables = tuple((int(w), tuple(m)) for w, m in kind_tables)
        key = (mesh, backend, kind_tables)
    else:
        modes = tuple(modes)
        key = (mesh, backend, modes)
    fn = _SHARD_CACHE.get(key)
    if fn is None:
        if backend == "pallas":
            if hetero:
                def body(w, h, kk):
                    return jnp.sum(
                        binpack_fitness_kinds_pallas(w, h, kk, kind_tables),
                        axis=1,
                    )
            else:
                def body(w, h):
                    return jnp.sum(
                        binpack_fitness_pallas(w, h, modes), axis=1
                    )
        elif backend == "ref":
            if hetero:
                def body(w, h, kk):
                    return jnp.sum(
                        binpack_fitness_kinds_ref(w, h, kk, kind_tables),
                        axis=1,
                    )
            else:
                def body(w, h):
                    return jnp.sum(binpack_fitness_ref(w, h, modes), axis=1)
        else:
            raise ValueError(
                f"unknown backend {backend!r}; options: auto, pallas, ref"
            )
        fn = _SHARD_CACHE[key] = row_shard(mesh, body)
    args = (widths, heights) + ((kinds,) if hetero else ())
    args, n = pad_rows(args, k)
    return fn(*(jnp.asarray(a) for a in args))[:n]
