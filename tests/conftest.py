"""Test configuration: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding paths can be exercised without TPU hardware.  Must run
before any test imports jax (backends initialise lazily, and no jax
computation has run yet at conftest import time).

Tier-1 is a CPU suite by definition; what runs on the chip is
``chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after XLA_FLAGS is set)

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "soak: long-horizon (1e5-frame) endurance tests; deselect with "
        '-m "not soak" when iterating',
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-thousand-tick stress runs (e.g. the bank fault soak); "
        "excluded from the tier-1 gate, run explicitly with -m slow",
    )
