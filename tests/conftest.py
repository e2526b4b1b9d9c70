"""Shared test fixtures.

NOTE: no XLA_FLAGS here — smoke tests must see the real single CPU device.
Multi-device tests (ring join, sharded store, replicas) spawn
subprocesses that set ``--xla_force_host_platform_device_count`` before
importing jax (see tests/util_subproc.py).
"""
import pytest

from repro.sparse.datagen import synthetic_sparse


@pytest.fixture(scope="session")
def small_rs():
    """A small (R, S) pair shared by join tests."""
    R = synthetic_sparse(48, dim=512, nnz_mean=20, nnz_std=5, seed=0)
    S = synthetic_sparse(80, dim=512, nnz_mean=20, nnz_std=5, seed=1)
    return R, S


def pytest_configure(config):
    # registered in pyproject.toml too; kept here so bare pytest invocations
    # from other rootdirs still know the markers
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "subproc: spawns subprocesses (multi-device virtual-CPU suites)")
