"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod mesh is 16x16 = 256 chips
("data", "model"); the multi-pod mesh adds a leading "pod" axis (2 pods =
512 chips) which composes with "data" for hierarchical data parallelism —
gradient all-reduces become (pod-local reduce-scatter, cross-pod all-reduce,
pod-local all-gather) under XLA's 2-D reduction lowering, the DCN-friendly
pattern.  A "pipe" axis for pipeline stages can be added here without any
model-code change (stage = slice of the scanned layer axis); see
docs/DESIGN.md section 5 for why the deployed configuration uses pod-DP
instead.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which slicing or
    reshaping a sharded output (``out[:n]`` after row padding, the fleet
    ops' ``(NP*P,) -> (NP, P)`` reshape) is a sharding type error.  With
    ``Auto`` axes the compiler propagates shardings through those ops."""
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(dryrun.py sets this automatically), or use make_host_mesh() / "
            "make_sweep_mesh(n) for CPU runs"
        )
    return auto_mesh(shape, axes, devices[:n])


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh for CPU smoke tests and examples."""
    return auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def make_sweep_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """1-D ``("prob",)`` mesh sharding the fleet's problem axis (PR 8).

    The sweep/portfolio fleet (docs/DESIGN.md section 14) is a problem-major
    array program; its only shardable axis is the leading problem axis, so
    the sweep mesh is one-dimensional.  ``n_devices=None`` takes every
    visible device.  On a CPU host, multiple devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"sweep mesh needs {n_devices} devices but only {len(devices)} "
            "present; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "for host-platform sharding"
        )
    return auto_mesh((n_devices,), ("prob",), devices[:n_devices])
