"""Test config: force an 8-device virtual CPU platform BEFORE jax import so
multi-device mesh tests run anywhere (SURVEY §4: the reference tests
distribution by spawning in-process pservers; we test it with a simulated
mesh — ``XLA_FLAGS=--xla_force_host_platform_device_count``)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_naming():
    """Reset auto layer names per test so topologies are reproducible."""
    from paddle_tpu.core import rng
    from paddle_tpu.layers import base

    base.reset_name_counters()
    rng.seed(7)
    yield


@pytest.fixture
def rng_np():
    return np.random.default_rng(0)
