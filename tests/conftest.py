"""Test harness: 8 virtual CPU devices, mirroring the reference's
``mpirun -np N`` localhost test strategy (SURVEY.md section 4/7)."""

import os
import sys
from os.path import abspath, dirname

# Must run before jax initializes its backends: force_host_device_count
# sets the 8-device flag in XLA_FLAGS and pins jax_platforms to the CPU, so
# the suite gets the virtual mesh whether or not a TPU is attached.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, dirname(dirname(abspath(__file__))))
from horovod_tpu.utils.platform import force_host_device_count  # noqa: E402

force_host_device_count(8, cpu=True)

import jax  # noqa: E402
import pytest  # noqa: E402

assert len(jax.devices()) >= 8, jax.devices()


@pytest.fixture(scope="session")
def n_devices():
    return len(jax.devices())


@pytest.fixture()
def hvd():
    """Fresh-initialized framework per test."""
    import horovod_tpu as hvd_mod
    hvd_mod.shutdown()
    hvd_mod.init()
    yield hvd_mod
    hvd_mod.shutdown()
