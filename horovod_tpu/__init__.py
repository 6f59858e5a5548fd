"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod (reference at /root/reference), built on JAX/XLA.

Layer map (TPU analog of reference SURVEY §1):

- ``horovod_tpu.parallel``  — device mesh + in-program XLA collectives
  (the data plane; replaces NCCL/MPI/Gloo ops).
- ``horovod_tpu.engine``    — native C++ coordination engine: async enqueue,
  rank-0 negotiation, tensor fusion planning, response cache, stall
  inspector, timeline (replaces horovod/common/*.cc).
- ``horovod_tpu.jax``       — the user-facing frontend: eager collectives,
  DistributedOptimizer/DistributedGradientTransform, compression, elastic
  state (replaces horovod/{torch,tensorflow}/ frontends).
- ``horovod_tpu.runner``    — launcher/orchestration: hvdrun-tpu CLI, host
  assignment, rendezvous KV, elastic driver (replaces horovod/runner/).
- ``horovod_tpu.models``, ``horovod_tpu.ops`` — benchmark model families and
  fused/pallas ops.
"""

import time as _time

_import_began = (_time.time(), _time.perf_counter())  # hvd.import, below

from horovod_tpu.version import __version__  # noqa: F401,E402

from horovod_tpu.common.basics import (  # noqa: F401,E402
    ccl_built,
    cross_rank,
    cross_size,
    cuda_built,
    ddl_built,
    engine_metrics,
    flight_dump,
    gloo_built,
    gloo_enabled,
    init,
    stall_report,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    num_replicas,
    rank,
    rocm_built,
    shutdown,
    size,
    start_timeline,
    stop_timeline,
)
from horovod_tpu.common.exceptions import (  # noqa: F401,E402
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.parallel import (  # noqa: F401,E402
    Adasum,
    Average,
    Max,
    Min,
    Op,
    Product,
    Sum,
    MeshSpec,
    build_mesh,
    data_parallel_mesh,
)


# Programmatic launcher (reference: horovod.run, runner/__init__.py:206).
from horovod_tpu.runner import run  # noqa: F401,E402

# The package's import as the span hvd.import of the program's compile log
# (what this file's imports cost, with whatever of theirs the process had
# not imported yet; benchmark/run.py imports jax first).
from horovod_tpu.metrics import compile_log as _compile_log  # noqa: E402

_compile_log.record("hvd.import", _import_began[0],
                    _time.perf_counter() - _import_began[1])
