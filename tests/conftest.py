"""Test configuration: a virtual 8-device CPU mesh so the whole stack —
including multi-"device" sharding — is testable without TPUs (fixing the
reference's biggest testing gap, SURVEY §4: every reference op/e2e test needs
real GPUs). Env vars must be set before jax is imported anywhere."""
import os
import sys

# hard-set (not setdefault): the environment may preset JAX_PLATFORMS to a
# real TPU platform, and tests must run on the virtual CPU mesh
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
# tests ask for no persistent compile cache by name: FFModel.compile places
# one in the checkout (utils/compile_cache.py), and a test run must neither
# depend on what an earlier run left there nor fill the checkout
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def mesh8():
    import jax
    from flexflow_tpu.parallel.mesh import build_mesh

    return build_mesh(mesh_shape=(4, 2), axis_names=("data", "model"))
