"""Chip test configuration (VERDICT.md round 3 ask 3).

Unlike tests/ (which forces a virtual 8-device CPU platform), this suite
runs the COMPILED kernels and needs a TPU: without one every test ERRORS —
a lost device must not read green. Run it on the chip machine, alone
(one process per chip): ``python -m pytest tests_tpu/ -q``.
"""

import pytest

import jax


@pytest.fixture(scope="session")
def tpu_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"tests_tpu needs a TPU; JAX reports {dev.platform!r}")
    return dev
