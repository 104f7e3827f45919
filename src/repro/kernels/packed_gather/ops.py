"""Jit'd wrapper: evaluate all logical matvecs of one packed bank."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import packed_gather_matvec
from .ref import packed_gather_ref


def bank_matvec(bank, x, seg, backend: str = "pallas"):
    if backend == "pallas":
        return packed_gather_matvec(bank, x, seg)
    return packed_gather_ref(bank, x, seg)


def split_outputs(y, seg, n_logical: int):
    """Scatter the fused (R,) result back into per-logical-buffer outputs."""
    return [y[jnp.asarray(seg) == n] for n in range(n_logical)]
