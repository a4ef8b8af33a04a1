"""Test harness config.

- Pins JAX to CPU with 8 virtual devices so multi-chip sharding tests run
  anywhere (the driver separately dry-runs the multichip path):
  JAX_PLATFORMS and XLA_FLAGS are set before jax is imported, and
  `jax_default_device` is pinned to the first CPU device.
- Runs `async def` tests on a fresh event loop (no pytest-asyncio in image).
"""

import asyncio
import gc
import inspect
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tier0: fast smoke suites (`make tier0`, < 60 s total)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate run")


@pytest.fixture
def cpu_mesh_devices():
    return jax.devices("cpu")


@pytest.fixture
def frozen_heap():
    """For a test that reads latencies off this process's event loop (the
    SLA gates): a full collection walks every object the EARLIER tests
    of this xdist worker left alive, and that pause lands on every
    stream in flight (446k objects after four engine files: the loop's
    worst stall 0.29 s against 0.04 s frozen, measured in PR 35).
    Freeze what is there; the test's own garbage is still collected."""
    gc.collect()
    gc.freeze()
    yield
    gc.unfreeze()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=120))
        return True
    return None
