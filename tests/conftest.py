"""Test harness: simulated 8-device CPU mesh.

The TPU-native analogue of the reference's multi-node-without-a-cluster
story (mp.spawn / docker-compose, SURVEY.md §4): XLA's forced host-platform
device count gives 8 fake devices on CPU, so every sharding/collective path
is exercised in CI without TPU hardware.

Provisioning logic lives in ``__graft_entry__._provision_cpu_mesh`` (the
driver hook needs the identical dance, and two copies would drift); it
defers the jax import, so it is safe to call before any backend exists and
works even when the environment already latched JAX_PLATFORMS.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _provision_cpu_mesh  # noqa: E402

_provision_cpu_mesh(8)

# Persistent XLA compilation cache, OPT-IN (TPUDML_TEST_CACHE=1): a
# developer's repeat runs reuse compiled executables; the default run —
# the driver's — stays cache-free so every run compiles what it tests.
# The directory follows the program's own rule (enable_compile_cache:
# JAX_COMPILATION_CACHE_DIR if set, else the fixed in-checkout one).
import jax  # noqa: E402

from tpudml.core.compile_cache import enable_compile_cache  # noqa: E402

if os.environ.get("TPUDML_TEST_CACHE"):
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
else:
    # Task entry points called in-process place the cache themselves.
    jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# tests/test_benchmarks_*.py star-import the benchmark's own test modules;
# registered here so their asserts are rewritten like a collected file's.
pytest.register_assert_rewrite("benchmarks.tests")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
