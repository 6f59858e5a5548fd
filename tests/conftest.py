"""Test harness: 8 virtual CPU devices, mirroring the reference's
multi-process test recipe (SURVEY §4: multiple processes on one machine).

Here a single process hosts an 8-device mesh — collectives execute for real
through XLA's CPU backend, exercising the same SPMD programs that run on a
TPU slice. Must run before jax initializes its backends, hence the env
mutation at conftest import time.
"""

import os
import socket

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch a chip; children inherit
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import pytest  # noqa: E402


# Ports for the subprocess drills. The kernel hands out its own ports from
# 32768 up, so nothing it gives a neighbouring test (a server bound to port
# 0, the far end of a connection) can fall in here, and each xdist worker
# allocates from a slice no other worker is given.
_PORT_BASE, _PORT_SLICE, _PORT_SLICES = 20000, 1000, 12
_ports_handed_out = 0


def free_port(span=2):
    """The first of ``span`` consecutive free ports. The engine listens on
    its controller port and on the next one (the data channel), and a
    subset communicator without a rendezvous KV adds small offsets to the
    controller port, so a caller asks for as many neighbours as its
    processes will bind. Every port is bound here before it is returned,
    and a worker hands no port out twice until its slice is used up."""
    global _ports_handed_out
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    index = int(worker[2:]) + 1 if worker.startswith("gw") else 0
    low = _PORT_BASE + (index % _PORT_SLICES) * _PORT_SLICE
    for _ in range(_PORT_SLICE // span):
        first = low + _ports_handed_out % (_PORT_SLICE - span + 1)
        _ports_handed_out += span
        try:
            for port in range(first, first + span):
                with socket.socket() as s:
                    s.bind(("", port))
        except OSError:
            continue
        return first
    raise RuntimeError(f"no {span} free ports in {low}-{low + _PORT_SLICE}")


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
