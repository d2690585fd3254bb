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

# The suite's CPU programs are compiled without LLVM's expensive passes
# (jax's own switch for "the cost of optimization is greater than that of
# running a less-optimized program"): toy sizes, where the compile is the
# time. The HLO and what the tests compare are the same; a third of the
# suite's CPU seconds go (test_lfm2.py alone: 186 -> 121 s user; PR 41). Set in
# the environment too, for the children the tests start (train.py,
# chip_smoke.py). tests/test_tpu_compile.py asks the TPU's compiler at its
# own settings (its `topo` fixture).
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import jax

jax.config.update("jax_platforms", "cpu")
if os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] == "1":  # (whoever imported jax before this file)
    jax.config.update("jax_disable_most_optimizations", True)

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
