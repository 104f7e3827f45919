"""Dispatcher for the fused portfolio step: GA fitness + SA deltas at once.

``portfolio_step`` is the device program behind ``core.portfolio``'s fused
barrier dispatch: one call evaluates a stacked GA generation's population
fitness (the ``binpack_fitness`` contract) AND one SA fleet annealing step's
touched-bin delta costs (the ``binpack_sa_step`` contract).  Backends:

* ``"python"`` — vectorized numpy for both halves; no JAX on the hot path.
* ``"ref"`` — ONE jit'd pure-jnp program computing both halves.
* ``"pallas"`` — the two Pallas kernels composed under one jit (a single
  compiled program per fused segment on TPU, interpreted elsewhere —
  ``kernels.platform``).
* ``"auto"`` — ``pallas`` when a TPU is attached, else ``ref``.

All backends use exact integer arithmetic: the returned totals are
bit-identical to ``binpack_fitness.ops.population_costs`` and the deltas to
``binpack_sa_step.ops.sa_step_deltas`` for the same inputs, so a fused
portfolio barrier cannot change any engine trajectory (pinned in
``tests/test_kernels.py`` and ``tests/test_portfolio_concurrent.py``).
"""
from __future__ import annotations

import numpy as np

from repro.core.problem import BRAM18_MODES
from repro.kernels.platform import on_tpu
from repro.kernels.binpack_sa_step.ops import (
    _bin_costs_kinds_numpy,
    _bin_costs_numpy,
)

BACKENDS = ("auto", "python", "ref", "pallas")


def portfolio_step(
    W,
    H,
    old_w,
    old_h,
    new_w,
    new_h,
    modes=BRAM18_MODES,
    backend: str = "auto",
    kinds=None,
    old_k=None,
    new_k=None,
    kind_tables=None,
    mesh=None,
) -> tuple[np.ndarray, np.ndarray]:
    """One fused call: ``(W, H)`` population geometry (any leading shape,
    bins on the last axis) plus ``(R, T)`` touched-bin SA step geometry ->
    ``(totals, deltas)``.

    ``totals`` is float64 with ``W``'s leading shape (exact integer values,
    matching ``GeneticPacker._batched_costs``); ``deltas`` is ``(R,)``
    int64 (matching ``sa_step_deltas``).  Heterogeneous problems pass the
    kind lanes of BOTH halves (``kinds`` for the populations, ``old_k`` /
    ``new_k`` for the touched slots) plus the shared ``kind_tables`` —
    all-or-none, since a portfolio's islands share one problem.

    ``mesh`` (a 1-D ``("prob",)`` sweep mesh) row-shards BOTH halves over
    their leading axes via ``shard_map`` on the jax backends, bit-identically
    (docs/DESIGN.md section 14); the ``"python"`` backend ignores it.
    """
    hetero = kind_tables is not None
    sides = (kinds is not None, old_k is not None, new_k is not None)
    if hetero != all(sides) or (not hetero and any(sides)):
        raise ValueError(
            "kinds/old_k/new_k/kind_tables must be passed together (the "
            "portfolio's islands share one problem) or not at all"
        )
    if backend == "auto":
        backend = resolve_auto()
    if hetero:
        kind_tables = tuple((int(w), tuple(m)) for w, m in kind_tables)
    else:
        modes = tuple(modes)
    if mesh is not None and backend in ("ref", "pallas"):
        return _portfolio_step_sharded(
            W, H, old_w, old_h, new_w, new_h, modes, backend,
            kinds, old_k, new_k, kind_tables, mesh,
        )
    if backend == "python":
        if hetero:
            per_bin = _bin_costs_kinds_numpy(W, H, kinds, kind_tables)
            new_c = _bin_costs_kinds_numpy(new_w, new_h, new_k, kind_tables)
            old_c = _bin_costs_kinds_numpy(old_w, old_h, old_k, kind_tables)
        else:
            per_bin = _bin_costs_numpy(W, H, modes)
            new_c = _bin_costs_numpy(new_w, new_h, modes)
            old_c = _bin_costs_numpy(old_w, old_h, modes)
        totals = per_bin.sum(axis=-1).astype(np.float64)
        return totals, np.sum(new_c - old_c, axis=-1)
    import jax.numpy as jnp

    if backend == "ref":
        if hetero:
            from .ref import portfolio_step_kinds_ref

            totals, deltas = _jit_ref_kinds()(
                jnp.asarray(W), jnp.asarray(H), jnp.asarray(kinds),
                jnp.asarray(old_w), jnp.asarray(old_h), jnp.asarray(old_k),
                jnp.asarray(new_w), jnp.asarray(new_h), jnp.asarray(new_k),
                kind_tables,
            )
        else:
            totals, deltas = _jit_ref()(
                jnp.asarray(W), jnp.asarray(H),
                jnp.asarray(old_w), jnp.asarray(old_h),
                jnp.asarray(new_w), jnp.asarray(new_h), modes,
            )
    elif backend == "pallas":
        if hetero:
            from .kernel import portfolio_step_kinds_pallas

            totals, deltas = portfolio_step_kinds_pallas(
                jnp.asarray(W), jnp.asarray(H), jnp.asarray(kinds),
                jnp.asarray(old_w), jnp.asarray(old_h), jnp.asarray(old_k),
                jnp.asarray(new_w), jnp.asarray(new_h), jnp.asarray(new_k),
                kind_tables,
            )
        else:
            from .kernel import portfolio_step_pallas

            totals, deltas = portfolio_step_pallas(
                jnp.asarray(W), jnp.asarray(H),
                jnp.asarray(old_w), jnp.asarray(old_h),
                jnp.asarray(new_w), jnp.asarray(new_h), modes,
            )
    else:
        raise ValueError(f"unknown backend {backend!r}; options: {BACKENDS}")
    return (
        np.asarray(totals, dtype=np.float64),
        np.asarray(deltas, dtype=np.int64),
    )


_SHARD_CACHE: dict = {}


def _portfolio_step_sharded(
    W, H, old_w, old_h, new_w, new_h, modes, backend,
    kinds, old_k, new_k, kind_tables, mesh,
) -> tuple[np.ndarray, np.ndarray]:
    """Row-sharded fused step over the ``("prob",)`` mesh (PR 8).

    The two halves carry different row counts (GA population stacks vs SA
    touched-bin rows), so each pads to a mesh-size multiple independently;
    one shard_map program still evaluates both.
    """
    import jax.numpy as jnp

    from repro.kernels.probshard import mesh_size, pad_rows, row_shard

    k = mesh_size(mesh)
    hetero = kind_tables is not None
    if hetero:
        key = (mesh, backend, kind_tables)
    else:
        key = (mesh, backend, modes)
    fn = _SHARD_CACHE.get(key)
    if fn is None:
        if backend == "ref":
            from .ref import portfolio_step_kinds_ref, portfolio_step_ref

            if hetero:
                def body(w, h, kk, ow, oh, ok, nw, nh, nk):
                    return portfolio_step_kinds_ref(
                        w, h, kk, ow, oh, ok, nw, nh, nk, kind_tables
                    )
            else:
                def body(w, h, ow, oh, nw, nh):
                    return portfolio_step_ref(w, h, ow, oh, nw, nh, modes)
        else:
            from .kernel import (
                portfolio_step_kinds_pallas,
                portfolio_step_pallas,
            )

            if hetero:
                def body(w, h, kk, ow, oh, ok, nw, nh, nk):
                    return portfolio_step_kinds_pallas(
                        w, h, kk, ow, oh, ok, nw, nh, nk, kind_tables,
                    )
            else:
                def body(w, h, ow, oh, nw, nh):
                    return portfolio_step_pallas(
                        w, h, ow, oh, nw, nh, modes
                    )
        fn = _SHARD_CACHE[key] = row_shard(mesh, body, n_outputs=2)
    pop = (W, H) + ((kinds,) if hetero else ())
    step = (
        (old_w, old_h, old_k, new_w, new_h, new_k)
        if hetero
        else (old_w, old_h, new_w, new_h)
    )
    pop, n_pop = pad_rows(pop, k)
    step, n_step = pad_rows(step, k)
    if hetero:
        w, h, kk = pop
        ow, oh, ok, nw, nh, nk = step
        args = (w, h, kk, ow, oh, ok, nw, nh, nk)
    else:
        w, h = pop
        ow, oh, nw, nh = step
        args = (w, h, ow, oh, nw, nh)
    totals, deltas = fn(*(jnp.asarray(a) for a in args))
    return (
        np.asarray(totals[:n_pop], dtype=np.float64),
        np.asarray(deltas[:n_step], dtype=np.int64),
    )


_REF_JIT = None
_REF_KINDS_JIT = None


def _jit_ref():
    global _REF_JIT
    if _REF_JIT is None:
        import functools

        import jax

        from .ref import portfolio_step_ref

        _REF_JIT = functools.partial(jax.jit, static_argnames=("modes",))(
            portfolio_step_ref
        )
    return _REF_JIT


def _jit_ref_kinds():
    global _REF_KINDS_JIT
    if _REF_KINDS_JIT is None:
        import functools

        import jax

        from .ref import portfolio_step_kinds_ref

        _REF_KINDS_JIT = functools.partial(
            jax.jit, static_argnames=("kind_tables",)
        )(portfolio_step_kinds_ref)
    return _REF_KINDS_JIT


def resolve_auto() -> str:
    """The fused-step "auto" policy: the Pallas composition on a real TPU,
    the jit'd reference elsewhere.  (The portfolio only routes barriers
    through the fused path when BOTH engine backends are jax-resolved, so
    on a CPU host — where SA auto-resolves to host numpy — fused dispatch
    stays off and this policy never demotes the hot path.)"""
    return "pallas" if on_tpu() else "ref"
