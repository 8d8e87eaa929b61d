"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; per the framework's test
strategy (SURVEY.md §4) all sharding/collective behavior is validated on
``--xla_force_host_platform_device_count=8`` CPU devices. The env must be
fixed before the first backend use, so XLA_FLAGS is set before jax is
imported and the platform is pinned via jax.config (the tests never touch
a chip; the chip is reached only through chip_smoke.py / bench.py).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# Persistent executable cache — the SAME helper recipes/bench use, so the
# suite and production runs share one cache policy ($JAX_COMPILATION_CACHE_DIR
# or <checkout>/.jax_cache). The suite is compile-dominated; a warm cache
# cuts re-runs. best_effort: an unwritable checkout must not stop the suite
# from collecting.
from pytorch_distributed_tpu.runtime.device import enable_compilation_cache

enable_compilation_cache(best_effort=True)


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Isolate tests from each other's process-group/mesh globals."""
    yield
    from pytorch_distributed_tpu.runtime import distributed, mesh, prng

    distributed.destroy_process_group()
    mesh.set_current_mesh(None)
    prng._BASE_KEY = None


@pytest.fixture
def mesh8():
    """2x2x2 (dp, fsdp, tp) mesh over the 8 virtual CPU devices."""
    from pytorch_distributed_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))


@pytest.fixture
def rng():
    return np.random.default_rng(0)
