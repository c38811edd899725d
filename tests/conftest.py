"""Test configuration: force an 8-virtual-device CPU platform so multi-chip
sharding paths (mesh placement, shard_map execution, collectives) are
exercised without TPU hardware.  Must run before jax initialises."""

import os

# Force CPU regardless of the ambient platform: the env var for child
# processes, the config update below for this one (backends init lazily
# on first device use).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: seconds-to-minutes end-to-end exercises (bench smoke, "
        "multihost) excluded from tier-1 via -m 'not slow'")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
