"""Test harness: fake an 8-device mesh on CPU.

This is the TPU analog of the reference's universal fake backend — CPU+Gloo
with N local processes (SURVEY.md §4): here a single process hosts 8 virtual
XLA CPU devices via ``--xla_force_host_platform_device_count``, so every
collective runs the real XLA partitioning/collective path without TPUs.

Must set env BEFORE jax initialises its backends, hence module scope here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    "expected 8 virtual CPU devices; backend was initialised too early")
import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "integration: spawns real worker subprocesses")
    config.addinivalue_line(
        "markers", "slow: multi-process chaos cases excluded from tier-1 "
        "(-m 'not slow') to protect its timeout budget; run with the full "
        "suite or -m slow")


@pytest.fixture(autouse=True)
def _fresh_context():
    """Each test gets a fresh (re-)initialised context."""
    hvd.shutdown()
    hvd.init()
    yield
    hvd.shutdown()


@pytest.fixture
def mesh8():
    return hvd.mesh()
