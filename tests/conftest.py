"""Test bootstrap: force an 8-device virtual CPU mesh before jax is imported.

Multi-chip shardings are validated on CPU (the driver separately dry-runs
``__graft_entry__.dryrun_multichip`` the same way); the chip is reached
through ``chip_smoke.py`` and ``benchmark/run.py`` outside pytest. Tests are
CPU-only by design: the platform is pinned here, in this process's own
environment, so neither pytest nor any child it spawns ever takes a chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from arkflow_tpu.utils.cleanenv import pin_cpu_env  # noqa: E402

pin_cpu_env(os.environ)

# Belt and braces: pin the default device to CPU so tests never compile on
# an accelerator.
import jax  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])

import asyncio
import inspect

import pytest


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests under asyncio.run (no pytest-asyncio in image)."""
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_runtest_teardown(item):
    """The bucket-cap bus is process-global (a device OOM in one test must
    not shrink coalescer grids built by later tests): forget announced caps
    after every test."""
    try:
        from arkflow_tpu.tpu.bucketing import bucket_cap_bus
    except ImportError:
        return
    bucket_cap_bus().reset()


@pytest.fixture(autouse=True, scope="module")
def _release_executables():
    """A process maps about ten regions of memory an executable it holds
    (measured here, PR 62: 12,561 mappings after ``test_nemotron_h.py``'s 55
    cases), pytest keeps every module it ran alive to the session's end —
    its jitted steps, its servers, its fixtures — and the kernel allows a
    process 65,530 (``vm.max_map_count``): a worker that has run enough
    files dies in the next executable it loads or serialises (``Fatal Python
    error: Segmentation fault`` under ``compilation_cache``, ROADMAP D0).
    Dropping JAX's caches where a module ends releases them."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()
