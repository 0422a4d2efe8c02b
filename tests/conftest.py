"""Test bootstrap: fake an 8-chip TPU slice with 8 CPU devices.

Reference parity: ChainerMN tested multi-node behavior with multi-process
single-node MPI (``mpiexec -n 8 pytest``, SURVEY.md §4).  We do one better —
single-process, 8 virtual devices — so the whole matrix runs anywhere.
MUST run before jax initializes its backend, hence module-level in conftest.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The CLIs place a persistent compile cache (topology.enable_compile_cache)
# and several tests call their main() in-process; the suite itself wants no
# cache — CPU entries are useless to the chip and a described-chip compile
# (tests/test_chip_compile.py) cannot be read back at all.
jax.config.update("jax_enable_compilation_cache", False)

import chainermn_tpu  # noqa: E402,F401

# Opt-in runtime lock-order cross-check (ISSUE 15 satellite): with
# CHAINERMN_TPU_LOCK_ASSERT=1 every threading.Lock/RLock created inside
# the package is replaced by a recording proxy, and the session-end
# fixture below asserts the UNION of the observed acquisition orders
# with the static lock graph stays acyclic — dynamic orders the AST
# cannot see (serving engines, routers, heartbeat threads in the
# serving test modules) are caught here.  Installed at import time so
# it precedes every lock construction in the tests.
from chainermn_tpu.analysis import lockassert as _lockassert  # noqa: E402

_LOCK_RECORDER = _lockassert.install_from_env()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lock_assert_gate():
    yield
    if _LOCK_RECORDER is not None:
        _LOCK_RECORDER.uninstall()
        _lockassert.assert_consistent(_LOCK_RECORDER)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: subprocess-spawning tests (larger virtual meshes)")
    config.addinivalue_line(
        "markers", "lint: SPMD static-analysis gate (pytest -m lint)")
