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
    # XLA:CPU sizes its thread pools to the machine's cores (or to
    # PJRT_NPROC), and a virtual device's blocking collective holds one
    # of the intra-op pool's threads. On an 8-core machine the 8 virtual
    # devices' all-reduces could take every thread while a participant
    # still waited for one: a deadlock that XLA ends by aborting the
    # process after 40 s ("Termination timeout ... Expected 4 threads to
    # join the rendezvous, but only 3 of them arrived"), which is what
    # took down a worker in test_pipeline.py's 60 pipelined train steps,
    # alone and unloaded in 1 run of 22 (0 of 120 with this). Two threads
    # a virtual device leaves room.
    os.environ.setdefault("PJRT_NPROC", str(max(16, os.cpu_count() or 1)))

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
