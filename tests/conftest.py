"""Test configuration: 8 virtual CPU devices + x64.

Multi-chip shardings are validated on a simulated mesh
(xla_force_host_platform_device_count), mirroring how the driver's
dryrun_multichip validates the real multi-chip path.  f64 is enabled for
ScaLAPACK-parity residual checks (SURVEY §7 hard-part (5)).
"""

import os

# Unit tests run on the CPU with the 8-device virtual mesh; both must be
# in the environment before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def grid22(devices):
    from slate_tpu.parallel.grid import ProcessGrid

    return ProcessGrid.from_devices(devices[:4], p=2, q=2)


@pytest.fixture(scope="session")
def grid42(devices):
    from slate_tpu.parallel.grid import ProcessGrid

    return ProcessGrid.from_devices(devices, p=4, q=2)


@pytest.fixture(scope="session")
def grid11(devices):
    from slate_tpu.parallel.grid import ProcessGrid

    return ProcessGrid.single(devices[0])


@pytest.fixture
def rng():
    return np.random.default_rng(42)
