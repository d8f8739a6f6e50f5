"""Test configuration: the tests run on the CPU, on an 8-device virtual
platform so the multi-chip sharding paths are exercised without
hardware.

Both settings go into the environment before jax is first imported
(the installed JAX honours ``JAX_PLATFORMS`` by itself).  The pin is a
default, not an override: a bare ``pytest`` on a machine with a TPU
must not take the chip, and the daemons the e2e tests spawn inherit it
— ``kwok --backend device`` refuses to start on the CPU unless
``JAX_PLATFORMS`` names ``cpu`` (kwok_tpu/utils/accel.py)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute multi-process e2e; deselected by the "
        "tier-1 run (-m 'not slow')",
    )
