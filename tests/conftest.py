"""Test configuration: JAX on the CPU with a virtual 8-device mesh, so the
multi-chip sharding tests run without a TPU.  Set before any test module
imports jax, which reads both variables when its backend starts."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tiers (budgeted fuzz search, full-profile "
        "differential replays) — excluded from the tier-1 gate via "
        "-m 'not slow'")
