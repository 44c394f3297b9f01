"""Test configuration.

Tests run on a virtual 8-device CPU platform so that multi-chip sharding /
comms paths are exercised without TPU hardware — the same trick the
reference uses with LocalCUDACluster on a single CI node (ref:
python/raft-dask/raft_dask/tests/conftest.py:14-35): the code path is
identical between the virtual mesh and a real pod.

Must run before jax initializes its backends, hence env mutation at import
time of this conftest (pytest imports it first).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Let the pairwise dispatch route through interpreted Pallas kernels on
# this CPU platform (production CPU callers keep the XLA path; the suite
# opts in to exercise the kernel code path).
os.environ.setdefault("RAFT_TPU_PALLAS_INTERPRET_DISPATCH", "1")

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP): long wall-clock load tests
    # (the Poisson serving soak) carry this marker; each slow test must
    # have a fast deterministic sibling that stays in tier-1
    config.addinivalue_line(
        "markers",
        "slow: long-running wall-clock tests excluded from tier-1")


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def res():
    """A fresh DeviceResources handle."""
    from raft_tpu.core import DeviceResources

    return DeviceResources(seed=42)
