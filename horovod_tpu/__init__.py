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

from horovod_tpu.version import __version__  # noqa: F401

from horovod_tpu.common.basics import (  # noqa: F401
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
from horovod_tpu.common.exceptions import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.parallel import (  # noqa: F401
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
