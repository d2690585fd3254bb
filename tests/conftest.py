"""Test harness: force an 8-device virtual CPU mesh before JAX backends init.

The suite runs on the CPU: ``XLA_FLAGS`` gives the CPU backend eight
virtual devices (how multi-chip sharding is validated without chips), and
``jax_platforms`` is pinned to ``cpu`` through ``jax.config`` — the pin is
also what tells the program's device check
(``sav_tpu/utils/device_check.py``) that the CPU was asked for.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: expensive mesh/pipeline/records tests; deselect with "
        "-m 'not slow' for the fast tier (<5 min on one core)",
    )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
