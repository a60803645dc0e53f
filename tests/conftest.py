"""Test harness configuration.

Tests run on CPU with a virtual 8-device platform so multi-chip sharding is
exercised without TPU hardware (mirrors the driver's dryrun_multichip
validation).  Env must be set before jax is imported anywhere.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compile cache: the suite's dominant cost is XLA compiles of the
# CCD kernel; caching them on disk makes reruns several times faster.
_cache = os.path.join(os.path.dirname(__file__), os.pardir, ".cache", "jax")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.abspath(_cache))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# The CCD oracle is float64; enable x64 so the JAX kernel can be tested at
# both precisions.
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def compile_cache_at(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR pointed at a fresh directory, every
    compile kept (CPU test kernels compile in under jax's 1 s default
    floor); jax's cache config is restored afterwards.  Yields the path."""
    from jax.experimental.compilation_cache import compilation_cache

    path = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        yield path
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
        compilation_cache.reset_cache()


def free_port() -> int:
    """An OS-assigned free TCP port (shared by the multihost coordinator
    and the ops-endpoint tests; small bind race accepted)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
