"""Pallas TPU kernel: fused packed-bank read + MAC (segment matvec).

The inference-side analogue of the paper's multi-port BRAM bins: several
logical weight matrices are co-located row-wise in one physical bank
(rows % sublane == 0, cols % 128 == 0).  One kernel pass streams the bank
HBM->VMEM once and computes every co-located logical output:

    y[r] = sum_c bank[r, c] * x[seg[r], c]

where seg[r] names which logical buffer row r belongs to (cardinality <= C
descriptors per bank, the paper's port constraint).  Without packing, each
logical buffer would be a separate (padded) array and a separate DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

ROW_TILE = 8  # fp32 sublane tile


def _packed_gather_kernel(bank_ref, x_ref, seg_ref, y_ref, *, n_logical):
    bank = bank_ref[...]  # (TR, C)
    seg = seg_ref[...]  # (TR, 1) int32
    acc = jnp.zeros(bank.shape[:1] + (1,), jnp.float32)
    for n in range(n_logical):  # cardinality-bounded unrolled loop
        xn = x_ref[n, :]  # (C,)
        partial = jnp.sum(bank * xn[None, :], axis=1, keepdims=True)
        acc = jnp.where(seg == n, partial, acc)
    y_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def packed_gather_matvec(
    bank: jax.Array,  # (R, C) f32, R % 8 == 0, C % 128 == 0
    x: jax.Array,  # (N, C) f32 — one activation vector per logical buffer
    seg: jax.Array,  # (R,) int32 segment ids in [0, N)
    interpret: bool | None = None,  # None: compiled on TPU, else interpreted
) -> jax.Array:
    r, c = bank.shape
    n = x.shape[0]
    seg2 = seg.astype(jnp.int32).reshape(r, 1)
    out = pl.pallas_call(
        functools.partial(_packed_gather_kernel, n_logical=n),
        grid=(r // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, c), lambda i: (i, 0)),
            pl.BlockSpec((n, c), lambda i: (0, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(bank, x, seg2)
    return out[:, 0]
