"""Test configuration.

JAX-touching tests (the optional ML workload extension) run on a
virtual 8-device CPU mesh; everything else is pure Python. The env vars
must be set before jax initialises, hence here.
"""

import os
import sys
import pathlib

# hard-set (not setdefault): tests always run on the virtual 8-device
# CPU mesh, with the Pallas kernels in interpret mode, even on a host
# with a chip (tests/test_tpu_compile.py compiles for a described one).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import asyncio
import inspect

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "asyncio: run this coroutine test on a fresh event loop"
    )
    config.addinivalue_line(
        "markers",
        "slow: soak-style tests excluded from the tier-1 fast run "
        "(-m 'not slow'); run them explicitly or via `make soak`",
    )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests without pytest-asyncio (not installed)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
