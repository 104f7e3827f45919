"""Where the kernels run and where their compiles are cached.

Backend resolution has no fallback: when JAX cannot name its platform,
every ``auto`` resolver raises instead of quietly running a host path.
The compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise
uses one fixed, gitignored directory in the checkout.
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.ga import GeneticPacker
from repro.core.sa import SimulatedAnnealingPacker
from repro.kernels import platform
from repro.kernels.binpack_fitness.ops import population_costs
from repro.kernels.binpack_portfolio_step import ops as pf_ops
from repro.kernels.binpack_sa_step import ops as sa_ops
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_Z = np.zeros((2, 3), dtype=np.int32)
RESOLVERS = {
    "ga": lambda: GeneticPacker(backend="auto")._resolve_backend(),
    "sa": lambda: SimulatedAnnealingPacker(
        perturbation="swap", backend="auto")._resolve_backend(),
    "sa_step": sa_ops.resolve_auto,
    "portfolio_step": pf_ops.resolve_auto,
    "fitness": lambda: population_costs(_Z, _Z),
    "interpret": lambda: platform.resolve_interpret(None),
}


@pytest.mark.parametrize("name", sorted(RESOLVERS))
def test_backend_resolution_raises_without_a_platform(monkeypatch, name):
    def no_platform():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", no_platform)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        RESOLVERS[name]()


def test_cpu_resolution_and_explicit_interpret():
    assert jax.default_backend() == "cpu"
    assert GeneticPacker(backend="auto")._resolve_backend() == "ref"
    assert sa_ops.resolve_auto() == "python"
    assert pf_ops.resolve_auto() == "ref"
    assert platform.resolve_interpret(None) is True
    assert platform.resolve_interpret(False) is False
    assert platform.pallas_interpret("pallas") is True
    assert platform.pallas_interpret("ref") is False


@pytest.fixture
def restore_cache_config():
    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs")
    }
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("from_env", [False, True], ids=["fixed", "env"])
def test_compile_cache_dir(monkeypatch, tmp_path, restore_cache_config,
                           from_env):
    if from_env:
        # JAX reads the variable itself when it is imported
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == (str(tmp_path) if from_env else str(ROOT / ".jax_cache"))
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
