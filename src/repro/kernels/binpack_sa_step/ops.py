"""Dispatcher for the fused SA step: per-chain delta cost + Metropolis rule.

``sa_step_deltas`` is the hot primitive of the batched multi-chain annealer:
four (C, T) int32 planes (touched-bin geometry before/after one buffer-swap
move per chain), six with the RAM-kind lanes, reduce to a (C,) integer
delta-cost vector in a single call.  On the jax backends the planes cross to
the device as one stacked (4 or 6, C, T) int32 block: one host-to-device put
a step, whatever the plane count.  Backends:

* ``"python"`` — vectorized numpy; no JAX import on the hot path.  At SA's
  tiny per-step shapes (T = 2 * swap_moves) this is the fastest option on a
  CPU host, where per-call device dispatch would dominate.
* ``"ref"`` — jit'd pure-jnp oracle (one fused XLA computation per step).
* ``"pallas"`` — the Pallas TPU kernel (compiled on a TPU, interpreted
  elsewhere — ``kernels.platform``).
* ``"auto"`` — ``pallas`` when a TPU is attached, else ``python``.

All backends use exact integer arithmetic and return bit-identical deltas;
the annealer's trajectory therefore cannot depend on the backend choice.

The Metropolis *comparison* (``u < exp(-d_e / T)``) deliberately stays
host-side in float64 (`metropolis_mask`, or a conditional scalar draw in the
single-chain engine): the legacy scalar loop draws its uniform only for
uphill moves and compares against ``math.exp``, and the engine's
backend-bit-parity contract pins that exact stream and rounding.  Fusing the
compare into the f32 kernel would break parity for ~1-ulp boundary cases.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.problem import BRAM18_MODES
from repro.kernels.platform import on_tpu
from repro.spans import span

BACKENDS = ("auto", "python", "ref", "pallas")


def _bin_costs_numpy(w: np.ndarray, h: np.ndarray, modes) -> np.ndarray:
    w = np.asarray(w, dtype=np.int64)[..., None]
    h = np.asarray(h, dtype=np.int64)[..., None]
    mode_w = np.asarray([m[0] for m in modes], dtype=np.int64)
    mode_d = np.asarray([m[1] for m in modes], dtype=np.int64)
    per_mode = -(-w // mode_w) * -(-h // mode_d)  # ceil div
    return np.where(w[..., 0] > 0, np.min(per_mode, axis=-1), 0)


def _bin_costs_kinds_numpy(w, h, k, kind_tables) -> np.ndarray:
    """Per-slot unit cost with a RAM-kind lane selecting the mode table."""
    k = np.asarray(k)
    out = np.zeros(np.asarray(w).shape, dtype=np.int64)
    for ki, (weight, modes) in enumerate(kind_tables):
        out = np.where(k == ki, _bin_costs_numpy(w, h, modes) * int(weight), out)
    return out


def sa_step_deltas(
    old_w,
    old_h,
    new_w,
    new_h,
    modes=BRAM18_MODES,
    backend: str = "auto",
    old_k=None,
    new_k=None,
    kind_tables=None,
    mesh=None,
) -> np.ndarray:
    """(C, T) touched-bin geometry before/after -> (C,) int64 cost deltas.

    Empty slots (w == 0) cost nothing on either side, so rows may be
    zero-padded to a common touched-bin count.  Heterogeneous problems pass
    per-slot RAM-kind lanes ``old_k``/``new_k`` plus the problem's
    ``kind_tables`` (``(weight, modes)`` per kind): each slot is then costed
    on its own mode table, so a kind flip (same geometry, different kind) is
    just another delta.  All backends stay exact-integer and bit-identical.

    A leading *problem axis* is also accepted on every backend:
    ``(NP, C, T)`` inputs return ``(NP, C)`` deltas — one fused call for a
    fleet of padded problems' chain blocks (the DSE sweep path —
    docs/DESIGN.md section 10).  Padded problems are masked by the same
    zero-width convention as padded slots.

    ``mesh`` (a 1-D ``("prob",)`` mesh from ``launch.mesh.make_sweep_mesh``)
    row-shards the jax backends via ``shard_map``: rows zero-pad to a
    multiple of the mesh size and each device costs its contiguous block,
    bit-identically (exact integers — docs/DESIGN.md section 14).  The
    ``"python"`` backend is host numpy — single-device by nature — so it
    ignores ``mesh``.
    """
    if backend == "auto":
        backend = resolve_auto()
    if np.ndim(old_w) == 3:
        np_, c_, t_ = np.shape(old_w)
        flat = lambda a: None if a is None else np.reshape(np.asarray(a), (np_ * c_, t_))  # noqa: E731
        out = sa_step_deltas(
            flat(old_w), flat(old_h), flat(new_w), flat(new_h),
            modes=modes, backend=backend,
            old_k=flat(old_k), new_k=flat(new_k), kind_tables=kind_tables,
            mesh=mesh,
        )
        return out.reshape(np_, c_)
    hetero = old_k is not None
    if hetero:
        if new_k is None or kind_tables is None:
            raise ValueError("old_k/new_k/kind_tables must be passed together")
        kind_tables = tuple((int(w), tuple(m)) for w, m in kind_tables)
    if mesh is not None and backend in ("ref", "pallas"):
        return _sa_step_deltas_sharded(
            old_w, old_h, new_w, new_h, modes, backend,
            old_k, new_k, kind_tables, mesh,
        )
    if backend == "python":
        if hetero:
            new_c = _bin_costs_kinds_numpy(new_w, new_h, new_k, kind_tables)
            old_c = _bin_costs_kinds_numpy(old_w, old_h, old_k, kind_tables)
        else:
            new_c = _bin_costs_numpy(new_w, new_h, modes)
            old_c = _bin_costs_numpy(old_w, old_h, modes)
        return np.sum(new_c - old_c, axis=-1)
    if backend == "ref":
        fn = _jit_ref(hetero)
    elif backend == "pallas":
        from .kernel import sa_step_deltas_kinds_pallas, sa_step_deltas_pallas

        fn = sa_step_deltas_kinds_pallas if hetero else sa_step_deltas_pallas
    else:
        raise ValueError(f"unknown backend {backend!r}; options: {BACKENDS}")
    if hetero:
        planes, table = (old_w, old_h, old_k, new_w, new_h, new_k), kind_tables
    else:
        planes, table = (old_w, old_h, new_w, new_h), tuple(modes)
    # the put is made before the call so that each part of the round trip
    # is a span of its own (repro.spans)
    with span("repro.dispatch.h2d"):
        block = _put(np.stack(planes, dtype=np.int32))
    with span("repro.dispatch.launch"):
        out = fn(block, table)
    with span("repro.dispatch.d2h"):
        return np.asarray(out, dtype=np.int64)


def _put(block: np.ndarray):
    """The step's one host-to-device copy: the stacked plane block."""
    import jax

    return jax.device_put(block)


_SHARD_CACHE: dict = {}


def _sa_step_deltas_sharded(
    old_w, old_h, new_w, new_h, modes, backend,
    old_k, new_k, kind_tables, mesh,
) -> np.ndarray:
    """Row-sharded delta evaluation over the ``("prob",)`` mesh (PR 8)."""
    import jax.numpy as jnp

    from repro.kernels.probshard import mesh_size, pad_rows, row_shard

    k = mesh_size(mesh)
    hetero = old_k is not None
    if hetero:
        key = (mesh, backend, kind_tables)
    else:
        modes = tuple(modes)
        key = (mesh, backend, modes)
    fn = _SHARD_CACHE.get(key)
    if fn is None:
        if backend == "ref":
            from .ref import sa_step_deltas_kinds_ref, sa_step_deltas_ref

            if hetero:
                def body(ow, oh, ok, nw, nh, nk):
                    return sa_step_deltas_kinds_ref(
                        ow, oh, ok, nw, nh, nk, kind_tables
                    )
            else:
                def body(ow, oh, nw, nh):
                    return sa_step_deltas_ref(ow, oh, nw, nh, modes)
        else:
            from .kernel import (
                sa_step_deltas_kinds_pallas,
                sa_step_deltas_pallas,
            )

            if hetero:
                def body(*planes):
                    return sa_step_deltas_kinds_pallas(
                        jnp.stack(planes), kind_tables
                    )
            else:
                def body(*planes):
                    return sa_step_deltas_pallas(jnp.stack(planes), modes)
        fn = _SHARD_CACHE[key] = row_shard(mesh, body)
    if hetero:
        args = (old_w, old_h, old_k, new_w, new_h, new_k)
    else:
        args = (old_w, old_h, new_w, new_h)
    args, n = pad_rows(args, k)
    out = fn(*(jnp.asarray(a) for a in args))
    return np.asarray(out[:n], dtype=np.int64)


def metropolis_mask(d_e, temps, u) -> np.ndarray:
    """Vectorized Metropolis rule: accept downhill, else ``u < exp(-d/T)``.

    Float64 throughout, matching the scalar loop's ``math.exp`` comparison.
    ``T <= 0`` freezes uphill moves entirely (greedy descent).
    """
    d = np.asarray(d_e, dtype=np.float64)
    t = np.asarray(temps, dtype=np.float64)
    u = np.asarray(u, dtype=np.float64)
    safe_t = np.where(t > 0, t, 1.0)
    p = np.exp(-np.maximum(d, 0.0) / safe_t)
    return (d < 0) | ((t > 0) & (u < p))


@functools.cache
def _jit_ref(hetero: bool):
    """The jit'd oracle over a stacked plane block (kind tables or modes
    static)."""
    import jax

    from .ref import sa_step_deltas_kinds_ref, sa_step_deltas_ref

    ref = sa_step_deltas_kinds_ref if hetero else sa_step_deltas_ref
    return jax.jit(lambda block, table: ref(*block, table), static_argnums=1)


def resolve_auto() -> str:
    """The SA "auto" policy: the Pallas kernel on a real TPU, host numpy
    everywhere else (per-step shapes are too small for CPU device dispatch
    to pay off)."""
    return "pallas" if on_tpu() else "python"
