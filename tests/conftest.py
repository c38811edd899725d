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


def over_budget_limit(holder) -> int:
    """The largest device-budget limit (one device's) that the dense
    forms of ``holder``'s own fragments do not fit — so its sparse
    fragments go compressed-resident (``DeviceBudget.dense_fits``,
    docs/memory-budget.md "The rule") — with as much room for what is
    held as that allows.  Reckoned from the holder alone: what other
    tests left alive in the process only adds to the budget's demand,
    and what the collector takes away mid-test cannot make it fit."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    own = sum(fr.n_rows for *_, fr in holder.iter_fragments()) * (128 << 10)
    return (own * 9 // 8 - 1) // DEFAULT_BUDGET.spread
