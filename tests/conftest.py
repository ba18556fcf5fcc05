"""Test config: force an 8-device virtual CPU mesh so sharding/collective
code paths are exercised without TPU hardware (the analog of the reference's
multi-process-on-localhost dist tests, test_dist_base.py:213)."""

import os

# Force CPU even if the ambient environment points JAX at a TPU: the suite
# needs 8 virtual devices. jax reads both variables no earlier than its
# own import, and nothing imports jax before this file (with or without
# xdist), so setting them here is enough. Set PTPU_TEST_REAL_DEVICE=1
# to opt out.
if not os.environ.get("PTPU_TEST_REAL_DEVICE"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

# The suite does not turn on jax's persistent compilation cache for its
# own process, but the replicas and examples it starts as subprocesses
# do (paddle_tpu/utils/compile_cache.py: `.jax_cache/` in the checkout,
# or JAX_COMPILATION_CACHE_DIR). An older jaxlib gave deserialized
# XLA:CPU executables that diverged ~1e-4 from the compile that wrote
# them and crashed under the in-process SIGTERM chaos cell. Re-checked
# on jax/jaxlib 0.9.0 with every compile forced through the cache
# (min compile time 0): a train step run cold, then from the cache, is
# bit-identical, and test_chaos, test_resilience, test_serve_http,
# test_kvxfer, test_async_frontdoor, test_fleet_ft, test_kvtier,
# test_tp_serve, test_goodput, test_engine and test_spec_decode pass
# both cold and warm — so neither holds any more.

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)
