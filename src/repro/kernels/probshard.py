"""shard_map plumbing for the 1-D ``("prob",)`` sweep mesh (PR 8).

The three batched bin-packing kernels (``binpack_fitness``,
``binpack_sa_step``, ``binpack_portfolio_step``) are row programs: every
operand carries the fleet's problem/chain rows on its leading axis and all
rows are independent.  Sharding them across a ``launch.mesh.make_sweep_mesh``
mesh is therefore purely mechanical:

1. zero-pad each operand's leading axis to a multiple of the mesh size
   (cost-neutral by the zero-width masking contract of DESIGN.md section 10
   — a padded row has width 0 everywhere and contributes cost 0),
2. run the kernel body under ``shard_map`` with every operand row-sharded
   over ``"prob"`` (``sharding.rules.prob_axis_spec``) so each device costs
   its own contiguous row block,
3. slice the padding back off the row-major outputs.

All kernels use exact integer arithmetic, so the sharded result is
bit-identical to the unsharded one — pinned in ``tests/test_sharded.py``.

Compiled sharded callables are cached per (mesh, static-config) key by the
ops modules; this module only holds the shared padding/wrapping helpers so
the jit caches stay hot across the annealer's per-iteration calls.
"""
from __future__ import annotations

import numpy as np


def mesh_size(mesh) -> int:
    """Width of the ``"prob"`` axis (validates the mesh is a sweep mesh)."""
    try:
        return int(mesh.shape["prob"])
    except (KeyError, TypeError) as e:
        raise ValueError(
            "mesh= must be a 1-D ('prob',) sweep mesh "
            "(launch.mesh.make_sweep_mesh); got axes "
            f"{getattr(mesh, 'axis_names', mesh)!r}"
        ) from e


def pad_rows(arrays, k: int):
    """Zero-pad each array's leading axis to a multiple of ``k`` rows.

    Returns ``(padded, n)`` where ``n`` is the original row count; callers
    slice outputs back with ``out[:n]``.  Zero rows are cost-free under the
    zero-width masking contract, so padding never perturbs results.
    """
    ns = {np.shape(a)[0] for a in arrays if a is not None}
    if len(ns) != 1:
        raise ValueError(f"operands disagree on row count: {sorted(ns)}")
    (n,) = ns
    pad = (-n) % k
    if pad == 0:
        return tuple(arrays), n
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
            continue
        a = np.asarray(a)
        block = np.zeros((pad,) + a.shape[1:], dtype=a.dtype)
        out.append(np.concatenate([a, block], axis=0))
    return tuple(out), n


def row_shard(mesh, fn, n_outputs: int = 1):
    """Wrap ``fn(*row_arrays)`` in jit(shard_map) over the ``"prob"`` axis.

    Every positional input and every output is row-sharded on its leading
    axis; trailing axes are replicated.  ``fn`` must close over its static
    configuration (mode tables, interpret flag) — callers cache the wrapped
    function per static key so jit compiles once per configuration.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import prob_axis_spec

    def run(*arrays):
        in_specs = tuple(prob_axis_spec(a.ndim) for a in arrays)
        if n_outputs == 1:
            out_specs = P("prob")
        else:
            out_specs = tuple(P("prob") for _ in range(n_outputs))
        # check_vma=False: jax has no varying-axes rule for pallas_call,
        # and nothing here relies on that check (every operand and output
        # is explicitly row-sharded or replicated).
        body = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return body(*arrays)

    return jax.jit(run)
