"""Test configuration: force an 8-fake-device CPU platform.

Multi-worker semantics (shard_map, all_gather, psum) are exercised exactly on
fake CPU devices (SURVEY.md §4 test strategy). The platform is pinned through
jax.config as well as expected from ``JAX_PLATFORMS=cpu``, so a bare
``pytest tests/`` on a machine that holds a chip still runs on the CPU.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Persist EVERY compiled program, not only those over JAX's 1 s default: the
# suite starts ~50 worker/CLI processes, each of which otherwise re-compiles
# the same ~135 sub-second eager programs (measured: a repeated `train.py
# --cpu_mesh 8` process drops from 20.9 s to 14.6 s). Set in the environment,
# before jax is imported, so every subprocess inherits it.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# many tests compile the same programs; the persistent cache (shared with the
# worker and CLI subprocesses) compiles each distinct program once per run
from dgc_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from dgc_tpu.parallel import make_mesh
    assert len(jax.devices()) >= 8, "conftest failed to create 8 CPU devices"
    return make_mesh(8)


@pytest.fixture
def rec():
    """``dgc_tpu.telemetry.trace`` switched on with a fresh recorder; the
    switch goes back afterwards."""
    from dgc_tpu.telemetry import trace
    prev = trace.enable(False)
    trace.enable(True)
    yield trace
    trace.enable(False)
    trace.enable(prev)
