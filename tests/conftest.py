"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (reference analog: ray.cluster_utils.Cluster
single-machine multi-node simulation; SURVEY.md §4). Env vars must be set before anything
imports jax, hence module level here.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# Belt and braces for a jax that something imported before this file set the
# environment: the config value is what backend selection reads.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rt():
    """Session-wide ray_tpu cluster. Worker pool recovers from destructive tests."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"}, max_workers_per_node=8)
    yield ray_tpu
    ray_tpu.shutdown()
