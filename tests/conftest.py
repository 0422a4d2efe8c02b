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


@pytest.fixture(scope="session")
def kept_as_declared():
    """The contract the serving pool relies on, as a check: the buffers a
    layer's serving entry (``blocks.LAYER_KINDS[kind].serve``) hands back
    after a whole prompt (``path='prefill'``) or one token a slot
    (``'tick'``) have the shapes and dtypes ``blocks.cache_layout`` and
    ``blocks.buffer_shape`` declare for that layer — what the pool
    allocated and writes them into."""
    def check(params, arch, head_dim, layer, path, mesh, n=2, total=16):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from chainermn_tpu._compat import shard_map
        from chainermn_tpu.parallel import blocks
        from chainermn_tpu.parallel.decode import _Core, _Work, _kv_heads

        arch = blocks.resolve(arch)
        dtype = params["embed"].dtype
        declared = blocks.cache_layout(
            arch, len(params["blocks"]),
            _kv_heads(params, head_dim, arch) * head_dim, "model")[layer]
        want = [(blocks.buffer_shape(buf, n, total), jnp.dtype(
            (buf[1] if blocks.is_state(buf) else None) or dtype))
            for buf in declared]

        def fn(params):
            bufs = tuple(jnp.zeros(*sd) for sd in want)
            s_q = total // 2 if path == "prefill" else 1
            x = jnp.zeros((n, s_q, params["embed"].shape[1]), dtype)
            if path == "prefill":
                core = _Core(params, head_dim, "model", arch)
                work = _Work(jnp.arange(s_q), 0)
            else:
                pos = jnp.arange(3, 3 + n)
                core = _Core(params, head_dim, "model", arch,
                             jnp.ones((n, 1), bool))
                work = _Work(pos[:, None], pos, {})
            y, new = blocks.layer_kind(arch, layer).serve(
                core, x, params["blocks"][layer], bufs, layer, work)
            assert y.shape == x.shape and y.dtype == x.dtype
            return new

        new = jax.eval_shape(shard_map(fn, mesh=mesh, in_specs=(P(),),
                                       out_specs=P()), params)
        assert isinstance(new, tuple)
        assert [(b.shape, b.dtype) for b in new] == want

    return check


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: subprocess-spawning tests (larger virtual meshes)")
    config.addinivalue_line(
        "markers", "lint: SPMD static-analysis gate (pytest -m lint)")
