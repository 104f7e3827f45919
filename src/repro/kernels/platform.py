"""The one place that decides where the packer's kernels run.

On a TPU the Pallas kernels compile for the chip; everywhere else (CPU
test hosts under ``JAX_PLATFORMS=cpu``) they run in the Pallas
interpreter.  Nothing here catches an error: when JAX cannot bring up a
platform, ``jax.default_backend()`` raises, and so does every backend
resolution built on it, instead of quietly running a host path.
"""
from __future__ import annotations


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret flag of a Pallas call.  ``None`` decides from the platform:
    compiled on a TPU, interpreted elsewhere.  An explicit flag is kept
    (CPU tests pass ``True``; compile-only tests for a described chip pass
    ``False``)."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def pallas_interpret(backend: str) -> bool:
    """Whether an engine whose kernels resolved to ``backend`` runs them in
    the Pallas interpreter (reported in result params)."""
    return backend == "pallas" and resolve_interpret(None)
