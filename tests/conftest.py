"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's ``test.NewCluster(n)`` fake-topology approach
(test/cluster.go:24-55): tests exercise real sharding logic on virtual
devices so multi-chip paths are validated without TPU pods.

The platform is pinned here with ``jax.config.update`` so that a bare
``pytest`` never reaches for an accelerator, whatever JAX_PLATFORMS
says; the XLA device-count flag works because backends initialize
lazily.
"""
import os

# The suite runs without the persistent compile cache (the variable is
# inherited by every server and worker child a test starts): a test
# must not depend on, or leave behind, what an earlier run compiled, and
# XLA:CPU logs a spurious machine-feature mismatch on every cached load.
# tests/test_chip_smoke.py turns it back on where the cache is the
# subject.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "faults: chaos suite — deterministic fault injection, "
        "fail-stop, graceful drain (run alone via `make chaos`)")
    config.addinivalue_line(
        "markers",
        "slow: boots real subprocess servers / long soaks — excluded "
        "from the tier-1 `-m 'not slow'` run, included in `make test`")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
