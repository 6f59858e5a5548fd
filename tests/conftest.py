"""Test harness: 8 virtual CPU devices, mirroring the reference's
multi-process test recipe (SURVEY §4: multiple processes on one machine).

Here a single process hosts an 8-device mesh — collectives execute for real
through XLA's CPU backend, exercising the same SPMD programs that run on a
TPU slice. Must run before jax initializes its backends, hence the env
mutation at conftest import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch a chip; children inherit
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def dp_mesh(devices):
    from horovod_tpu.parallel import mesh as mesh_lib
    return mesh_lib.data_parallel_mesh(devices)


@pytest.fixture(autouse=True)
def _reset_context():
    """Each test sees a fresh framework context."""
    yield
    import horovod_tpu
    if horovod_tpu.is_initialized():
        horovod_tpu.shutdown()
