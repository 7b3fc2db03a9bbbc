"""Test configuration.

Tests run on CPU with 8 virtual devices so sharding/mesh tests exercise real
multi-device paths without TPU hardware (SURVEY.md §4 "distributed without a
cluster"). The chip path is exercised by chip_smoke.py and tests_tpu/.

This must run before jax initializes its backends, hence env vars set at
import time (conftest imports before test modules).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # never the chip: one process owns it
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# float64 enabled globally: gradient checks require double precision
# (reference: DataType.DOUBLE for GradCheckUtil); float32 paths pass explicit
# dtypes everywhere so this does not change their behavior.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture
def rng():
    from deeplearning4j_tpu.core import RngState

    return RngState(12345)


@pytest.fixture(autouse=True)
def _reset_environment():
    yield
    from deeplearning4j_tpu.core import get_environment

    get_environment().reset()
