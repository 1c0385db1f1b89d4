"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's distributed-in-one-box strategy (tests/unit/common.py
``DistributedTest``): multi-chip semantics are exercised on one host. Here a
single process drives 8 XLA cpu devices through the same GSPMD code paths the
TPU pod uses.

The CPU is forced three ways before the first backend use: ``JAX_PLATFORMS``
(inherited by every child process a test spawns), the ``jax_platforms``
config (wins even if jax was imported before this file), and ``XLA_FLAGS``
for the cpu client's device count (the client is created lazily, so this is
still in time).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_sessionstart(session):  # noqa: ARG001
    devs = jax.devices()
    assert devs[0].platform == "cpu", (
        f"test suite must run on the virtual CPU mesh, got {devs[0].platform}: "
        "a backend was initialized before conftest could force cpu"
    )


@pytest.fixture(autouse=True)
def _fresh_topology():
    """Each test builds its own mesh; reset the singleton between tests."""
    import deepspeed_tpu.parallel.mesh as mesh_mod

    mesh_mod.reset_topology()
    yield
    mesh_mod.reset_topology()


@pytest.fixture(autouse=True)
def _clear_jax_caches():
    """Drop compiled executables after EVERY test. Accumulated
    executables/live buffers degrade the 8-device CPU mesh pathologically
    (observed 2026-07-31: test_spatial runs 43s fresh but sat >45 min at
    full CPU when reached through the suite; a module-scoped clear moved
    the wedge into the next large module instead of removing it). The
    recompilation cost is a few seconds per test; the wedge it prevents is
    unbounded."""
    yield
    jax.clear_caches()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs
