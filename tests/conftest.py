"""Test configuration: force an 8-device virtual CPU mesh.

The JAX analog of the reference's loopback-socket integration testing
(``src/test/federated_api_test.ts`` spins a real socket.io server on
localhost): we spin a real 8-device mesh on fake CPU devices so every
collective/sharding path is exercised without TPU hardware.

Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an accelerator
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from distriflow_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# The suite's wall time is dominated by compiles; the persistent cache lets
# a repeated run on one machine skip them (placement rule:
# distriflow_tpu/utils/compile_cache.py).
enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


# -- smoke/full tiers (round 3) -------------------------------------------
# Modules whose tests are multi-minute (compile-heavy models, real
# multi-process jax.distributed, soak loops). The smoke tier skips them:
#   python -m pytest -m "not slow"
# Marking by MODULE keeps new tests in a heavy module automatically slow.
_SLOW_TEST_MODULES = {
    "test_transformer",
    "test_pipelined_transformer",
    "test_generate",
    "test_ulysses",
    "test_multiprocess",
    "test_multihost_train",
    "test_failover",
    "test_distributed_checkpoint",
    "test_sharded_checkpoint",
    "test_keras_rnn",
    "test_tp_decode",
    "test_mobilenet",
    "test_streaming",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = getattr(item, "module", None)
        if mod is not None and mod.__name__ in _SLOW_TEST_MODULES:
            item.add_marker(pytest.mark.slow)
