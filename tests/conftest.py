"""Test environment: the CPU with 8 virtual devices, always.

Mirrors the reference's test pyramid decision (SURVEY.md §4): multi-"node"
behavior is exercised on one host. A virtual 8-device CPU platform stands
in for a TPU slice so sharding/collective paths compile and run in CI
without TPU hardware. Must run before any jax import.

The suite never runs on a chip: several xdist workers cannot share one.
The chip is reached through `chip_smoke.py`, one process per chip; what
the TPU compiler accepts is checked without a chip in
tests/test_chip_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# jax.config overrides a *registered* backend, but is a silent no-op once
# a backend is *initialized* — check so tests fail loudly instead of
# running on whatever device an earlier import picked.
jax.config.update("jax_platforms", "cpu")
if not (jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8):
    # Not a bare assert: that would be compiled out under python -O.
    raise RuntimeError(
        f"test env needs 8 virtual CPU devices, got {jax.devices()}; a "
        "backend was initialized before conftest ran"
    )

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "multidevice: needs >=4 devices (virtual CPU mesh or slice)"
    )
    config.addinivalue_line(
        "markers",
        "slow: long chaos/workload drives, excluded from tier-1 "
        "(opt in with tools/run_tier1.sh --chaos or -m slow)",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)
