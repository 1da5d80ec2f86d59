"""Test configuration: force an 8-device virtual CPU platform BEFORE jax
imports so mesh/sharding logic is exercised without TPU hardware
(SURVEY.md §4 "TPU-without-TPU")."""

import os

# the suite is a CPU suite wherever it runs: say so here rather than rely
# on the ambient JAX_PLATFORMS
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from sitewhere_tpu.runtime.compilecache import enable_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# persistent compile cache: the suite re-jits the same shapes every run.
# The chip tool copies the checkout, cache included: at this threshold a
# whole tier-1 run leaves under 10 MB there (CHANGES.md, PR 24)
enable_compile_cache(min_compile_secs=0.3)


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests via asyncio.run (no pytest-asyncio in image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (run via asyncio.run)")


@pytest.fixture
def bus():
    from sitewhere_tpu.runtime.bus import EventBus

    return EventBus()
