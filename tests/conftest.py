import os

# Force CPU with 8 virtual devices BEFORE jax import anywhere in tests.
# (Parity with reference test strategy: fake resources / simulated multi-node,
# SURVEY.md §4 — JAX-side tests use host-platform virtual devices.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent compilation cache: the suite compiles many small programs
# (often identical across test processes/runs); caching them on disk cuts
# total suite wall time substantially. Placed from outside: JAX reads the
# variable itself, and spawned workers inherit it.
from ray_tpu._private.node import default_compile_cache_dir  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scale/chaos tests (deselect with -m 'not slow' "
        "for the fast tier)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: network fault-injection tests (the bounded smoke variants "
        "run in the default tier; full soaks are additionally marked slow)",
    )


@pytest.fixture
def tmp_store(tmp_path):
    from ray_tpu._private.object_store import SharedMemoryStore

    store = SharedMemoryStore.create(str(tmp_path / "store"), 64 * 1024 * 1024)
    yield store
    store.close()


@pytest.fixture
def rt():
    """A running single-node cluster, shut down after the test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rt_tune():
    """Shared tune-suite cluster (4 CPUs, small store)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()
