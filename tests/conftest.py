"""Test configuration: force an 8-device virtual CPU mesh before jax imports.

Multi-chip shardings are validated on virtual CPU devices (the real
environment has a single TPU chip); the driver's dryrun_multichip does the
same. x64 is enabled because score math is int64 (framework.MaxNodeScore
scale, reference pkg/scheduler/framework/interface.go:95) and resource math
is int64 milli-units.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"  # force: the session env may point at a TPU
os.environ["JAX_ENABLE_X64"] = "1"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak tests, excluded from tier-1 (-m 'not slow')",
    )


def _memory_maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop jax's compiled-program caches between test modules once the
    process holds too many memory mappings. Every XLA:CPU executable
    keeps some, the suite compiles thousands of distinct shapes, and in
    one process the count reaches vm.max_map_count (65530) about three
    quarters of the way through: the next compile then dies with SIGSEGV
    inside LLVM. The largest module adds ~10k, so 30k leaves room; below
    it the caches stay, because modules do share their small shapes."""
    yield
    if _memory_maps() > 30_000:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def sim_mesh():
    """8-device simulated CPU mesh over the node axis — the tier-1 stand-in
    for a real multi-host topology (the module docstring's XLA_FLAGS recipe
    provides the virtual devices). Parametrize shard counts by slicing:
    `Mesh(np.asarray(jax.devices()[:n]), ("nodes",))` or
    `make_mesh(n_devices=n)`."""
    from kubernetes_tpu.parallel.sharded import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(n_devices=8)
