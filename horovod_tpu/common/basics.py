"""Process-level context: init/shutdown/rank/size and the default mesh.

Mirrors the surface of the reference's ``horovod/common/basics.py`` (init,
shutdown, rank, size, local_rank, local_size, cross_rank, cross_size,
is_initialized, start_timeline, stop_timeline) — reference basics.py:27-258 —
but TPU-native underneath:

- topology comes from the launcher env contract (``HOROVOD_RANK`` etc., same
  variable names the reference's gloo launcher exports,
  reference: horovod/runner/gloo_run.py:65-78) or defaults to a single
  process;
- the *device* dimension is a `jax.sharding.Mesh` over this process's (or the
  job's) devices — replica count = processes × local devices;
- when the native coordination engine is available (horovod_tpu.engine), init
  also boots its background thread for the eager/async collective path.
"""

from __future__ import annotations

import subprocess
import threading
from typing import Optional, Sequence

import jax

from horovod_tpu.common.env_registry import (env_bool, env_int, env_is_set,
                                             env_str)
from horovod_tpu.parallel import mesh as mesh_lib


class _HorovodTpuContext:
    """Singleton process context (reference analog: HorovodGlobalState,
    horovod/common/global_state.h:43-132, minus the engine internals which
    live in the native library)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.initialized = False
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self._has_host_map = False
        self.mesh = None
        self.engine = None  # native engine session, when booted
        self.metrics_exporter = None  # HOROVOD_METRICS_PORT endpoint
        self.elastic = False

    def init(self,
             mesh_spec: Optional[mesh_lib.MeshSpec] = None,
             devices: Optional[Sequence[jax.Device]] = None,
             start_engine: Optional[bool] = None,
             comm: Optional[Sequence[int]] = None):
        """The compile log (metrics/compile_log.py) listens from here on and
        holds this call as the span ``hvd.init``, with a child for each part
        that runs: rendezvous, mesh, engine, exporter."""
        if self.initialized:
            return
        from horovod_tpu.metrics import compile_log
        compile_log.install()
        with compile_log.span("hvd.init"):
            self._init(mesh_spec, devices, start_engine, comm)

    def _init(self, mesh_spec, devices, start_engine, comm):
        from horovod_tpu.metrics.compile_log import span
        with self._lock:
            if self.initialized:
                return
            # Python logging honors the same HOROVOD_LOG_LEVEL /
            # HOROVOD_LOG_TIMESTAMP the C++ engine reads (logging.cc).
            from horovod_tpu.common.hvd_logging import (
                set_rank_context, setup_python_logging)
            setup_python_logging()
            from horovod_tpu.runner.elastic import worker as elastic_worker
            if elastic_worker.is_elastic_worker():
                # Synchronize with the driver's current topology generation
                # (READY/go barrier) before reading the env it rewrites —
                # both on first spawn and on elastic re-init (reference:
                # gloo_context.cc:154-200 re-init scope query).
                with span("hvd.init.rendezvous"):
                    elastic_worker.rendezvous()
            # Topology: launcher env contract first; failing that, a live
            # jax.distributed job defines the process world — otherwise a
            # multi-host job launched outside hvdrun-tpu would read size=1
            # and every "single-process" fallback would silently diverge.
            jaxd = jax.process_count() if jax.process_count() > 1 else 1
            self.rank = env_int("HOROVOD_RANK",
                                jax.process_index() if jaxd > 1 else 0)
            self.size = env_int("HOROVOD_SIZE", jaxd)
            self.local_rank = env_int("HOROVOD_LOCAL_RANK")
            self.local_size = env_int("HOROVOD_LOCAL_SIZE")
            self.cross_rank = env_int("HOROVOD_CROSS_RANK", self.rank)
            self.cross_size = env_int("HOROVOD_CROSS_SIZE", self.size)
            # A host-locality map exists only when the launcher actually
            # exported one — the env defaults above (cross_rank=rank)
            # would otherwise make every rank of a hand-rolled
            # multi-process job look like its own host, silently turning
            # on the engine's topology exchange (and with it the
            # hierarchical route, degenerate at one rank per "host").
            self._has_host_map = (env_is_set("HOROVOD_CROSS_RANK") or
                                  env_is_set("HOROVOD_CROSS_SIZE"))
            # From here on every hvd_logging record carries rank/local_rank
            # so multi-rank logs interleave legibly (re-stamped below if a
            # comm= subset re-ranks this process).
            set_rank_context(self.rank, self.local_rank)
            self.elastic = env_bool("HOROVOD_ELASTIC")
            # Process-subset communicator (reference: hvd.init(comm=[ranks]),
            # operations.cc:712-714 + mpi_context.cc:126-138 MPI_Group_incl):
            # members re-rank into the subset; non-members become size-1
            # singletons excluded from the job's collectives.
            subset_ports = None  # (controller, data) override for comm=
            in_subset = False
            if comm is not None:
                members = sorted({int(r) for r in comm})
                world = self.size
                bad = [r for r in members if r < 0 or r >= world]
                if bad:
                    raise ValueError(
                        f"comm ranks {bad} outside the world of {world}")
                # every rank counts every init(comm=...) round — members of
                # different successive subsets would otherwise skew their
                # counters and disagree on the round-scoped ports
                global _subset_round
                _subset_round += 1
                if self.rank in members:
                    in_subset = True
                    subset_ports = _negotiate_subset_ports(
                        members, is_leader=self.rank == members[0])
                    if subset_ports is None:
                        # no rendezvous KV (hand-rolled env): arithmetic
                        # offset — distinct per disjoint subset AND per
                        # init round (all members init in lockstep, so
                        # their round counters agree), though not reserved
                        # against other services
                        base = env_int("HOROVOD_CONTROLLER_PORT")
                        if base:
                            off = base + 2 * (1 + members[0] +
                                              world * (_subset_round - 1))
                            subset_ports = (off, off + 1)
                    self.rank = members.index(self.rank)
                    self.size = len(members)
                    self.cross_rank = self.rank
                    self.cross_size = self.size
                    # synthetic cross dims — the subset's physical host
                    # placement is unknown, so no locality map
                    self._has_host_map = False
                    # keep the context self-consistent: world-scoped local
                    # dims can exceed the subset (local placement of the
                    # other members is unknown here)
                    if self.local_size > self.size:
                        self.local_rank = self.rank
                        self.local_size = self.size
                else:
                    import warnings
                    warnings.warn(
                        f"rank {self.rank} is not in comm={members}; "
                        "continuing as a size-1 singleton outside the job")
                    self.rank = 0
                    self.size = 1
                    self.cross_rank, self.cross_size = 0, 1
                    self._has_host_map = False
                set_rank_context(self.rank, self.local_rank)
            try:
                with span("hvd.init.mesh"):
                    self.mesh = mesh_lib.build_mesh(mesh_spec, devices)
                if start_engine is None:
                    # The engine serves the eager multi-process path
                    # (broadcast_object, metric_average, elastic State.sync).
                    # Its host-TCP controller coexists with a jax.distributed
                    # SPMD job, so it boots whenever the process world is >1 —
                    # otherwise those ops would silently return local results
                    # and diverge across replicas. Pure-SPMD jobs that never
                    # touch the eager path can pass start_engine=False; a
                    # jax.distributed job launched outside hvdrun-tpu (no
                    # controller rendezvous in the env) gets that default,
                    # and eager ops raise loudly rather than degrade.
                    start_engine = self.size > 1 and (
                        env_is_set("HOROVOD_SIZE") or
                        env_is_set("HOROVOD_CONTROLLER_PORT"))
                if start_engine:
                    from horovod_tpu.common.exceptions import \
                        HorovodInternalError
                    from horovod_tpu.engine import bindings
                    try:
                        with span("hvd.init.engine"):
                            self.engine = bindings.EngineSession(
                                rank=self.rank, size=self.size,
                                local_rank=self.local_rank,
                                local_size=self.local_size,
                                # Locality map for the topology-aware data
                                # plane: the launcher's host index, or -1
                                # (flat) for single-host jobs and jobs whose
                                # cross dims are synthetic defaults.
                                host_id=self.cross_rank
                                if self._has_host_map and self.cross_size > 1
                                else -1,
                                port=subset_ports[0] if subset_ports else None,
                                data_port=subset_ports[1] if subset_ports
                                else None)
                    except (ImportError, OSError, ValueError,
                            HorovodInternalError,
                            subprocess.CalledProcessError) as e:
                        hint = ""
                        if in_subset:
                            hint = (" Note: subset communicators "
                                    "(init(comm=...)) require the lowest "
                                    "comm rank to run on the controller "
                                    "host (HOROVOD_CONTROLLER_ADDR) — its "
                                    "engine hosts the subset's "
                                    "coordination endpoint.")
                        raise RuntimeError(
                            "the native coordination engine could not be "
                            "loaded/built (run `make -C horovod_tpu/engine`); "
                            "pass init(start_engine=False) for a pure-SPMD "
                            f"run without the eager path.{hint} "
                            f"Cause: {e}") from e
                # Prometheus endpoint — off by default, one per worker when
                # HOROVOD_METRICS_PORT is set (metrics/exporter.py).
                if env_is_set("HOROVOD_METRICS_PORT"):
                    from horovod_tpu.metrics import start_exporter_from_env
                    with span("hvd.init.exporter"):
                        self.metrics_exporter = start_exporter_from_env(
                            rank=self.rank, engine=self.engine)
                self.initialized = True
            except BaseException:
                self.mesh = None
                self.engine = None
                raise

    def shutdown(self):
        with self._lock:
            if not self.initialized:
                return
            if self.metrics_exporter is not None:
                self.metrics_exporter.stop()
                self.metrics_exporter = None
            if self.engine is not None:
                self.engine.shutdown()
                self.engine = None
            self.mesh = None
            self.initialized = False
            from horovod_tpu.metrics import compile_log
            compile_log.uninstall()


_ctx = _HorovodTpuContext()


def _context() -> _HorovodTpuContext:
    return _ctx


_subset_round = 0


def _negotiate_subset_ports(members, is_leader: bool):
    """Reserve the subset's controller/data ports through the launcher's
    rendezvous KV (collision-free, unlike arithmetic offsets): the lowest
    member allocates free ports on its host — where its engine will bind —
    and publishes them; other members poll. Returns (port, data_port) or
    None when no rendezvous KV is in the env."""
    import time
    addr = env_str("HOROVOD_RENDEZVOUS_ADDR")
    port = env_int("HOROVOD_RENDEZVOUS_PORT")
    if not addr or not port:
        return None
    from horovod_tpu.runner.http_kv import (KVClient,
                                            replica_endpoints_from_env)
    client = KVClient(addr, port, endpoints=replica_endpoints_from_env())
    # per-init round counter (incremented by the caller; all members call
    # init in lockstep), so a second init(comm=...) in the same processes
    # can't read the previous round's — now closed — ports
    from horovod_tpu.common import kv_keys
    key = kv_keys.subset_ports(members, _subset_round)
    if is_leader:
        from horovod_tpu.runner.launch import free_ports
        ports = tuple(free_ports(2))
        client.put_json(key, {"port": ports[0], "data_port": ports[1]})
        return ports
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        info = client.get_json(key, timeout=5.0)
        if info:
            return (int(info["port"]), int(info["data_port"]))
        time.sleep(0.2)
    raise RuntimeError(
        f"subset leader never published ports for comm={members}")


def _single_process() -> bool:
    """True when size-1 semantics apply (uninitialized counts as size 1).
    The one shared predicate behind every local-fallback fast path — eager
    ops raise (rather than degrade) when this is False and the engine is
    absent."""
    return (_ctx.size if _ctx.initialized else 1) == 1


def _require_init():
    if not _ctx.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call horovod_tpu.init().")


def init(mesh_spec: Optional[mesh_lib.MeshSpec] = None,
         devices: Optional[Sequence[jax.Device]] = None,
         start_engine: Optional[bool] = None,
         comm: Optional[Sequence[int]] = None):
    """Initialize the framework (reference: hvd.init, basics.py:33-65).
    ``comm``: optional list of global ranks forming the working communicator
    (reference: init(comm=[ranks]), operations.cc:712-714); other processes
    continue as size-1 singletons. The lowest comm rank must run on the
    controller host (HOROVOD_CONTROLLER_ADDR) — its engine hosts the
    subset's coordination endpoint."""
    _ctx.init(mesh_spec=mesh_spec, devices=devices, start_engine=start_engine,
              comm=comm)


def shutdown():
    """Tear down (reference: hvd.shutdown, basics.py:67-73)."""
    _ctx.shutdown()


def is_initialized() -> bool:
    return _ctx.initialized


def rank() -> int:
    """Global process rank (reference: basics.py:141-150)."""
    _require_init()
    return _ctx.rank


def size() -> int:
    """Number of processes (reference: basics.py:123-131)."""
    _require_init()
    return _ctx.size


def local_rank() -> int:
    _require_init()
    return _ctx.local_rank


def local_size() -> int:
    _require_init()
    return _ctx.local_size


def cross_rank() -> int:
    _require_init()
    return _ctx.cross_rank


def cross_size() -> int:
    _require_init()
    return _ctx.cross_size


def num_replicas() -> int:
    """Total data-parallel replicas.

    The reference has exactly one device per rank so this equals size();
    on TPU one process drives many chips, so the DP world is larger than the
    process world. Gradient averaging / LR scaling uses this count.

    Two multi-process shapes exist:
    - ``jax.distributed`` SPMD: the mesh is built over the job's *global*
      devices, so its data×fsdp extent already counts every replica.
    - engine-coordinated separate processes: each process has a local mesh;
      replicas = size × local extent.
    """
    _require_init()
    m = _ctx.mesh
    extent = m.shape["data"] * m.shape["fsdp"] if m is not None else 1
    if jax.process_count() > 1:
        return extent
    return _ctx.size * extent


def mesh():
    """The process's default device mesh."""
    _require_init()
    return _ctx.mesh


def is_homogeneous() -> bool:
    """Reference: basics.py:183-189 (same local_size on every host)."""
    _require_init()
    return True


def mpi_threads_supported() -> bool:
    """Build-capability parity shim (reference: basics.py:191-206). The TPU
    build has no MPI; the eager path is always thread-safe."""
    return True


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    """The native TCP controller plays the role Gloo plays in the reference."""
    return True


def gloo_built() -> bool:
    return True


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def engine_metrics() -> Optional[dict]:
    """Runtime metrics snapshot of this process's engine session
    (``Session.metrics()``), or None when no engine is running. The
    Prometheus exporter serves the same data as ``hvd_engine_*`` families;
    this is the programmatic view."""
    _require_init()
    return _ctx.engine.metrics() if _ctx.engine is not None else None


def stall_report() -> Optional[dict]:
    """The last stall-inspector report observed by this rank (ready/missing
    ranks per stalled tensor, machine-readable), or None. Available on
    EVERY rank — the coordinator broadcasts each new report."""
    _require_init()
    return _ctx.engine.stall_report() if _ctx.engine is not None else None


def flight_dump(dir: Optional[str] = None) -> Optional[dict]:
    """On-demand collective flight-recorder dump of this process's engine
    session (``Session.flight_dump()``), or None when no engine is
    running. When ``dir`` is given, also writes
    ``<dir>/flight_rank<R>.json`` for the cross-rank post-mortem analyzer
    (``python -m horovod_tpu.profiler.flight <dir>``). The engine dumps
    automatically to ``HOROVOD_FLIGHT_DIR`` on abort, on a fresh stall
    report, and on SIGUSR2."""
    _require_init()
    return _ctx.engine.flight_dump(dir) if _ctx.engine is not None else None


def start_timeline(file_path: str, mark_cycles: bool = False):
    """Start engine timeline capture (reference: basics.py:75-98)."""
    _require_init()
    if _ctx.engine is None:
        raise RuntimeError("timeline requires the native engine (size>1 or "
                           "init(start_engine=True))")
    _ctx.engine.start_timeline(file_path, mark_cycles)


def stop_timeline():
    _require_init()
    if _ctx.engine is not None:
        _ctx.engine.stop_timeline()
