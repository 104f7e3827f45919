"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled at RN152-W1A2 widths
(NB = 2253 buffers on the U50 inventory) for a chip that is described, not
attached, and the compiled program must contain the Pallas kernel
(``tpu_custom_call``).  This catches what interpret mode cannot — tiling,
fast-memory limits, partitioning — without a chip.  The topology is
described inside a fixture, never at import; the file skips where it cannot
be described.  JAX's persistent compilation cache is off around the
compiles: a program compiled for a described chip cannot be read back.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import repro.core as c
from repro.kernels.binpack_fitness.kernel import (
    binpack_fitness_kinds_pallas,
    binpack_fitness_pallas,
)
from repro.kernels.binpack_portfolio_step.kernel import (
    portfolio_step_kinds_pallas,
    portfolio_step_pallas,
)
from repro.kernels.binpack_sa_step.kernel import (
    sa_step_deltas_kinds_pallas,
    sa_step_deltas_pallas,
)

_NAME = "RN152-W1A2"
N_POP = c.hyperparams(_NAME)["n_pop"]  # the GA's stacked fitness rows
SA_ROWS = 64  # fleet rows P*C: one problem x 64 chains
SA_WIDTH = 2 * 2  # 2 * swap_moves touched-bin slots per step
PF_ISLANDS, PF_POP, PF_ROWS = 2, 50, 8  # default portfolio: 2 GA + 8 chains


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def prob():
    p = c.get_problem(_NAME, device="U50")
    assert p.n == 2253 and p.n_kinds == 2
    return p


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
def test_binpack_fitness_compiles(prob, one_chip, kinds):
    x = _i32((N_POP, prob.n), one_chip)
    if kinds:
        low = binpack_fitness_kinds_pallas.lower(
            x, x, x, prob.kind_tables, False
        )
    else:
        low = binpack_fitness_pallas.lower(x, x, c.BRAM18_MODES, False)
    _assert_kernel(low)


@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
def test_binpack_sa_step_compiles(prob, one_chip, kinds):
    if kinds:
        block = _i32((6, SA_ROWS, SA_WIDTH), one_chip)
        low = sa_step_deltas_kinds_pallas.lower(block, prob.kind_tables, False)
    else:
        block = _i32((4, SA_ROWS, SA_WIDTH), one_chip)
        low = sa_step_deltas_pallas.lower(block, c.BRAM18_MODES, False)
    _assert_kernel(low)


@pytest.mark.parametrize("kinds", [False, True], ids=["plain", "kinds"])
def test_binpack_portfolio_step_compiles(prob, one_chip, kinds):
    pop = _i32((PF_ISLANDS, PF_POP, prob.n), one_chip)
    step = _i32((PF_ROWS, SA_WIDTH), one_chip)
    if kinds:
        low = portfolio_step_kinds_pallas.lower(
            pop, pop, pop, step, step, step, step, step, step,
            prob.kind_tables, False,
        )
    else:
        low = portfolio_step_pallas.lower(
            pop, pop, step, step, step, step, c.BRAM18_MODES, False
        )
    _assert_kernel(low)


def test_row_sharded_fitness_compiles_on_four_chips(prob, topo):
    from repro.kernels.probshard import row_shard
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4,), ("prob",), topo.devices[:4])
    kt = prob.kind_tables

    def body(w, h, k):
        return jnp.sum(binpack_fitness_kinds_pallas(w, h, k, kt, False), axis=1)

    rows = NamedSharding(mesh, P("prob", None))
    x = _i32((4 * N_POP, prob.n), rows)
    low = row_shard(mesh, body).lower(x, x, x)
    compiled = low.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert {d.id for d in compiled.output_shardings.device_set} == {
        d.id for d in topo.devices[:4]
    }
