"""Test fixtures (modeled on the reference's python/ray/tests/conftest.py:
ray_start_regular :294, ray_start_cluster :375, shutdown_only :223).

JAX tests run on a virtual 8-device CPU mesh: the env vars MUST be set before
jax is imported anywhere in the process (fake-accelerator mode, the JAX
equivalent of the reference's _fake_gpus)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu._private.jax_env import ensure_compile_cache  # noqa: E402

# One persistent XLA compilation cache for the test process AND every
# worker it spawns (they inherit the environment): gang workers otherwise
# recompile identical programs on every spawn/rebuild, which dominates
# suite wall-clock.  Must run before anything imports jax.
ensure_compile_cache()


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024**2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()
