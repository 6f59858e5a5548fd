"""ctypes bindings to the native coordination engine (libhvdtpu_core.so).

Reference analog: horovod/common/basics.py loading the framework .so via
ctypes (basics.py:27-65) — here the library is framework-neutral and
session-based, so a single test process can host N engine ranks coordinating
over the in-process loopback transport.

Env knobs honored (same names as the reference, common/common.h:65-93):
HOROVOD_CYCLE_TIME (ms), HOROVOD_FUSION_THRESHOLD (bytes),
HOROVOD_CACHE_CAPACITY, HOROVOD_STALL_CHECK_TIME_SECONDS,
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, HOROVOD_STALL_CHECK_DISABLE,
HOROVOD_TIMELINE, HOROVOD_TIMELINE_MARK_CYCLES,
HOROVOD_CONTROLLER_TIMEOUT_SECONDS (TCP transport recv timeout; plays the
role of HOROVOD_GLOO_TIMEOUT_SECONDS).
"""

from __future__ import annotations

import ctypes
import json
import subprocess
import threading
from pathlib import Path
from typing import Callable, Optional, Sequence

from horovod_tpu.common.env_registry import (env_bool, env_float, env_int,
                                             env_str)
from horovod_tpu.common.exceptions import HorovodInternalError

# Engine wire dtype ids (engine/src/common.h DataType).
DTYPE_IDS = {
    "uint8": 0, "int8": 1, "uint16": 2, "int16": 3, "int32": 4,
    "int64": 5, "float16": 6, "float32": 7, "float64": 8, "bool": 9,
    "bfloat16": 10,
}
DTYPE_NAMES = {v: k for k, v in DTYPE_IDS.items()}

# Op ids (engine/src/common.h OpType).
OP_ALLREDUCE = 0
OP_ALLGATHER = 1
OP_BROADCAST = 2
OP_ALLTOALL = 3
OP_JOIN = 4
OP_BARRIER = 5

_EXECUTE_CB = ctypes.CFUNCTYPE(ctypes.c_int32, ctypes.c_char_p,
                               ctypes.c_void_p)

_lib = None
_lib_lock = threading.Lock()

# Must match hvdtpu_abi_version() in src/c_api.cc; bumped together with any
# semantic ABI change so a stale prebuilt .so is rejected at load time.
# 6: hvdtpu_abort + hvdtpu_set_fault_spec; hvdtpu_wait can return
#    StatusType::CORRUPTED (6) -> HorovodCorruptedError.
# 7: hvdtpu_flight_dump + hvdtpu_bench_flight_record (collective flight
#    recorder); Request wire format carries a signature hash.
# 8: hvdtpu_step_begin/hvdtpu_step_end — frontend step-boundary marks
#    recorded into the flight ring (step-time attribution); DONE flight
#    events carry the response's exec-callback span (us) in aux.
# 9: hvdtpu_set_tuned_params / hvdtpu_get_tuned_params — runtime push of
#    cycle time / fusion threshold / cache / express-lane knobs through
#    the parameter-sync broadcast (HOROVOD_TUNE); the TunedParams wire
#    record gains low_latency_threshold_bytes + express_lane.
# 10: topology-aware data plane — hvdtpu_create_session gains host_id
#     (launcher locality map; loopback multi-host simulation);
#     hvdtpu_set_tuned_params gains ring_threshold_bytes / hierarchical /
#     small_tensor_algo (cycle-fenced routing); hvdtpu_data_algo_ops.
ABI_VERSION = 10

# TunedParams.small_tensor_algo ids (engine/src/data_plane.h).
SMALL_TENSOR_ALGOS = {"star": 0, "rd": 1}


def _lib_path() -> Path:
    return Path(__file__).parent / "build" / "libhvdtpu_core.so"


def build_library(force: bool = False) -> Path:
    # Explicit library override (e.g. the TSan build in build-tsan/): trust
    # the caller, skip make — the ABI check below still rejects stale ones.
    override = env_str("HOROVOD_ENGINE_LIB")
    if override:
        return Path(override)
    # Run make when a toolchain is present: its dependency tracking makes a
    # fresh build a no-op, and it protects against a stale prebuilt .so
    # missing newly added symbols (the .so is gitignored and survives
    # checkouts). Deploy images without make fall back to the prebuilt .so;
    # load_library's symbol setup fails loudly if that .so is stale.
    try:
        subprocess.run(["make", "-C", str(Path(__file__).parent)] +
                       (["-B"] if force else []),
                       check=True, capture_output=True)
    except FileNotFoundError:
        if _lib_path().exists():
            return _lib_path()
        raise
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            "engine build failed:\n" +
            (e.stderr or b"").decode(errors="replace")) from e
    return _lib_path()


def load_library():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = build_library()
        lib = ctypes.CDLL(str(path))
        try:
            lib.hvdtpu_abi_version.restype = ctypes.c_int32
            abi = lib.hvdtpu_abi_version()
        except AttributeError:
            abi = -1
        if abi != ABI_VERSION:
            raise HorovodInternalError(
                f"stale engine library {path}: ABI {abi}, expected "
                f"{ABI_VERSION} — rebuild with `make -C "
                f"{Path(__file__).parent}`")
        lib.hvdtpu_create_session.restype = ctypes.c_int64
        lib.hvdtpu_create_session.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_double, ctypes.c_double, ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_int32, ctypes.c_double,
            ctypes.c_double, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_int32,
        ]
        lib.hvdtpu_destroy_session.argtypes = [ctypes.c_int64]
        lib.hvdtpu_shutdown.argtypes = [ctypes.c_int64]
        for fn in ("hvdtpu_rank", "hvdtpu_size", "hvdtpu_local_rank",
                   "hvdtpu_local_size", "hvdtpu_healthy"):
            getattr(lib, fn).argtypes = [ctypes.c_int64]
            getattr(lib, fn).restype = ctypes.c_int32
        lib.hvdtpu_set_execute_callback.argtypes = [
            ctypes.c_int64, _EXECUTE_CB, ctypes.c_void_p]
        lib.hvdtpu_enqueue.restype = ctypes.c_int32
        lib.hvdtpu_enqueue.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_double, ctypes.c_double,
            ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.hvdtpu_join.argtypes = [ctypes.c_int64,
                                    ctypes.POINTER(ctypes.c_int64)]
        lib.hvdtpu_last_joined_rank.argtypes = [ctypes.c_int64]
        lib.hvdtpu_last_joined_rank.restype = ctypes.c_int32
        lib.hvdtpu_poll.restype = ctypes.c_int32
        lib.hvdtpu_poll.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_char_p, ctypes.c_int32]
        lib.hvdtpu_wait.restype = ctypes.c_int32
        lib.hvdtpu_wait.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_double, ctypes.c_char_p,
                                    ctypes.c_int32]
        lib.hvdtpu_start_timeline.argtypes = [ctypes.c_int64,
                                              ctypes.c_char_p,
                                              ctypes.c_int32]
        lib.hvdtpu_stop_timeline.argtypes = [ctypes.c_int64]
        lib.hvdtpu_timeline_activity_start.restype = ctypes.c_int32
        lib.hvdtpu_timeline_activity_start.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p]
        lib.hvdtpu_timeline_activity_end.restype = ctypes.c_int32
        lib.hvdtpu_timeline_activity_end.argtypes = [
            ctypes.c_int64, ctypes.c_char_p]
        lib.hvdtpu_last_error.restype = ctypes.c_char_p
        # data plane (callback-thread only)
        lib.hvdtpu_data_allreduce.restype = ctypes.c_int32
        lib.hvdtpu_data_allreduce.argtypes = [
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_double, ctypes.c_double]
        lib.hvdtpu_data_allgatherv.restype = ctypes.c_int64
        lib.hvdtpu_data_allgatherv.argtypes = [
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.hvdtpu_data_bcast.restype = ctypes.c_int32
        lib.hvdtpu_data_bcast.argtypes = [
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
        lib.hvdtpu_data_alltoallv.restype = ctypes.c_int64
        lib.hvdtpu_data_alltoallv.argtypes = [
            ctypes.c_int64, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64)]
        lib.hvdtpu_data_fetch.restype = ctypes.c_int32
        lib.hvdtpu_data_fetch.argtypes = [ctypes.c_int64, ctypes.c_void_p,
                                          ctypes.c_int64]
        lib.hvdtpu_data_ring_ops.restype = ctypes.c_int64
        lib.hvdtpu_data_ring_ops.argtypes = [ctypes.c_int64]
        lib.hvdtpu_data_algo_ops.restype = ctypes.c_int64
        lib.hvdtpu_data_algo_ops.argtypes = [ctypes.c_int64, ctypes.c_int32]
        lib.hvdtpu_metrics_snapshot.restype = ctypes.c_int64
        lib.hvdtpu_metrics_snapshot.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.hvdtpu_last_stall_report.restype = ctypes.c_int64
        lib.hvdtpu_last_stall_report.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.hvdtpu_flight_dump.restype = ctypes.c_int64
        lib.hvdtpu_flight_dump.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64]
        lib.hvdtpu_bench_flight_record.restype = ctypes.c_double
        lib.hvdtpu_bench_flight_record.argtypes = [ctypes.c_int64,
                                                   ctypes.c_int32]
        lib.hvdtpu_step_begin.restype = ctypes.c_int32
        lib.hvdtpu_step_begin.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.hvdtpu_step_end.restype = ctypes.c_int32
        lib.hvdtpu_step_end.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.hvdtpu_set_tuned_params.restype = ctypes.c_int32
        lib.hvdtpu_set_tuned_params.argtypes = [
            ctypes.c_int64, ctypes.c_double, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32]
        lib.hvdtpu_get_tuned_params.restype = ctypes.c_int64
        lib.hvdtpu_get_tuned_params.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.hvdtpu_abort.restype = ctypes.c_int32
        lib.hvdtpu_abort.argtypes = [ctypes.c_int64, ctypes.c_char_p]
        lib.hvdtpu_set_fault_spec.restype = ctypes.c_int32
        lib.hvdtpu_set_fault_spec.argtypes = [ctypes.c_char_p,
                                              ctypes.c_uint64]
        _lib = lib
        return _lib


def set_fault_spec(spec: str, seed: int = 0):
    """(Re)install a fault-injection spec for this process (the
    HOROVOD_FAULT_SPEC grammar — see engine/src/fault_injector.h). An empty
    spec disables injection; a malformed one raises so tests can't silently
    run without their faults."""
    lib = load_library()
    rc = lib.hvdtpu_set_fault_spec((spec or "").encode(), seed)
    if rc != 0:
        raise ValueError(lib.hvdtpu_last_error().decode())


def bench_flight_record(iters: int, enabled: bool = True) -> float:
    """ns per flight-recorder Record() call (``enabled=False`` times the
    disabled early-out; ``tests/test_flight_recorder.py`` reads the pair).
    Session-free: runs on a standalone recorder instance."""
    lib = load_library()
    return float(lib.hvdtpu_bench_flight_record(iters, 1 if enabled else 0))


class EngineSession:
    """One engine rank: background coordination thread + async handles."""

    def __init__(self,
                 rank: int,
                 size: int,
                 local_rank: int = 0,
                 local_size: int = 1,
                 host_id: Optional[int] = None,
                 transport: str = "tcp",
                 group: str = "default",
                 addr: Optional[str] = None,
                 port: Optional[int] = None,
                 data_port: Optional[int] = None,
                 cycle_time_ms: Optional[float] = None,
                 fusion_threshold: Optional[int] = None,
                 cache_capacity: Optional[int] = None,
                 stall_warning_sec: Optional[float] = None,
                 stall_shutdown_sec: Optional[float] = None,
                 timeout_sec: Optional[float] = None):
        self._lib = load_library()
        if host_id is None:
            # Launcher topology contract: HOROVOD_CROSS_RANK is this
            # worker's host index. A single-host job (HOROVOD_CROSS_SIZE
            # <= 1) passes -1 = "no locality map", keeping the data
            # plane's wire traffic byte-identical to the flat build.
            # Loopback tests simulate multi-host grouping by passing
            # distinct host_id values per in-process rank.
            host_id = env_int("HOROVOD_CROSS_RANK") \
                if env_int("HOROVOD_CROSS_SIZE") > 1 else -1
        addr = addr or env_str("HOROVOD_CONTROLLER_ADDR")
        port = port if port is not None else \
            env_int("HOROVOD_CONTROLLER_PORT")
        if transport == "tcp" and port <= 0:
            raise ValueError(
                "tcp transport needs HOROVOD_CONTROLLER_PORT (the launcher "
                "exports it; set it manually for hand-rolled runs)")
        data_port = data_port if data_port is not None else \
            env_int("HOROVOD_CONTROLLER_DATA_PORT")
        cycle_time_ms = cycle_time_ms if cycle_time_ms is not None else \
            env_float("HOROVOD_CYCLE_TIME")
        fusion_threshold = fusion_threshold if fusion_threshold is not None \
            else env_int("HOROVOD_FUSION_THRESHOLD")
        cache_capacity = cache_capacity if cache_capacity is not None else \
            env_int("HOROVOD_CACHE_CAPACITY")
        stall_warning_sec = stall_warning_sec if stall_warning_sec is not None\
            else env_float("HOROVOD_STALL_CHECK_TIME_SECONDS")
        stall_shutdown_sec = stall_shutdown_sec if stall_shutdown_sec is not \
            None else env_float("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS")
        stall_disable = env_bool("HOROVOD_STALL_CHECK_DISABLE")
        timeout_sec = timeout_sec if timeout_sec is not None else \
            env_float("HOROVOD_CONTROLLER_TIMEOUT_SECONDS")
        timeline_path = env_str("HOROVOD_TIMELINE") or ""
        timeline_cycles = env_bool("HOROVOD_TIMELINE_MARK_CYCLES")

        self._session = self._lib.hvdtpu_create_session(
            rank, size, local_rank, local_size, host_id,
            transport.encode(),
            (group if transport == "loopback" else addr).encode(),
            port, data_port, timeout_sec, cycle_time_ms, fusion_threshold,
            cache_capacity, 1 if cache_capacity > 0 else 0,
            stall_warning_sec, stall_shutdown_sec,
            1 if stall_disable else 0,
            timeline_path.encode() if timeline_path else None,
            1 if timeline_cycles else 0)
        if self._session <= 0:
            raise HorovodInternalError(
                "engine init failed: " +
                self._lib.hvdtpu_last_error().decode())
        self._cb_ref = None  # keep the CFUNCTYPE alive
        self._destroyed = False

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self):
        if not self._destroyed:
            self._lib.hvdtpu_shutdown(self._session)
            self.destroy()

    def abort(self, reason: str = ""):
        """Fast abort: fail every pending and future collective on EVERY
        rank within one coordination cycle (the abort flag + reason ride the
        next cycle's coordination exchange). Pending ``wait`` calls raise
        HorovodInternalError carrying ``reason``; the session is unusable
        afterwards — elastic recovery tears it down and re-inits."""
        if not self._destroyed:
            self._lib.hvdtpu_abort(self._session, reason.encode())

    def destroy(self):
        if not self._destroyed:
            self._lib.hvdtpu_destroy_session(self._session)
            self._destroyed = True

    # -- introspection ------------------------------------------------------

    @property
    def rank(self):
        return self._lib.hvdtpu_rank(self._session)

    @property
    def size(self):
        return self._lib.hvdtpu_size(self._session)

    @property
    def healthy(self):
        return self._lib.hvdtpu_healthy(self._session) == 1

    def data_ring_ops(self) -> int:
        """Collectives served by the ring data path (diagnostics)."""
        return self._lib.hvdtpu_data_ring_ops(self._session)

    def data_algo_ops(self, algo: str) -> int:
        """Collectives served by a data-plane routing algorithm:
        ``"ring"``, ``"rd"`` (recursive doubling), or ``"hier"``
        (hierarchical). Star = total minus these; the full per-algorithm
        breakdown (plus inter-host vs intra-host wire bytes) is in
        :meth:`metrics` under ``data_{star,ring,rd,hier}_ops``."""
        ids = {"ring": 0, "rd": 1, "hier": 2}
        return self._lib.hvdtpu_data_algo_ops(self._session, ids[algo])

    def _json_call(self, fn) -> Optional[dict]:
        """Shared buffer dance for the JSON-returning C calls: the return
        value is the full payload length, so one retry with a right-sized
        buffer always suffices."""
        size = 1 << 16
        for _ in range(4):
            buf = ctypes.create_string_buffer(size)
            n = fn(self._session, buf, size)
            if n < 0:
                raise HorovodInternalError("invalid engine session")
            if n < size:
                raw = buf.value.decode()
                return json.loads(raw) if raw else None
            # headroom, not exact fit: the payload may grow between the
            # probe and the retry (background thread keeps counting)
            size = max(n + 1, size * 2)
        raise HorovodInternalError("metrics snapshot kept growing")

    def metrics(self) -> dict:
        """Runtime metrics snapshot: {"rank", "counters", "gauges",
        "histograms"} — counters are monotonic, histogram buckets are
        per-bucket (not cumulative). The Prometheus exporter
        (horovod_tpu.metrics) converts these into `hvd_engine_*` families."""
        return self._json_call(self._lib.hvdtpu_metrics_snapshot) or {}

    def stall_report(self) -> Optional[dict]:
        """The last stall-inspector report observed by this rank, or None.
        {"stalled": [{"tensor", "ready", "missing", "waited_sec"}, ...],
        "warning_sec": N} — the coordinator broadcasts each new report so
        every rank can name the missing ranks (reference behavior analog:
        test_stall.py in the reference only sees rank-0 log text)."""
        return self._json_call(self._lib.hvdtpu_last_stall_report)

    def flight_dump(self, dir: Optional[str] = None) -> Optional[dict]:
        """On-demand flight-recorder dump: the black box of the last
        HOROVOD_FLIGHT_RECORDER_SIZE collective events on this rank
        ({"rank", "size", "trigger", "reason", "events": [...]}; see
        engine/src/flight_recorder.h). When ``dir`` is given, also writes
        ``<dir>/flight_rank<R>.json`` — the input of the cross-rank
        analyzer (``python -m horovod_tpu.profiler.flight <dir>``). The
        engine writes the same file automatically on abort, on a fresh
        stall report, and on SIGUSR2 when HOROVOD_FLIGHT_DIR is set."""
        d = (dir or "").encode()

        def call(session, buf, size):
            return self._lib.hvdtpu_flight_dump(session, d, buf, size)

        return self._json_call(call)

    def step_begin(self, step_id: int):
        """Record a frontend step-boundary STEP_BEGIN mark (flight ring)
        for the step-time attribution engine. One lock-free flight Record —
        cheap enough for every train-step invocation. Driven automatically
        by the ``hvd_frontend_step_seconds`` step-timer wrapper."""
        if not self._destroyed:
            self._lib.hvdtpu_step_begin(self._session, step_id)

    def step_end(self, step_id: int):
        """Record the matching STEP_END mark (see :meth:`step_begin`)."""
        if not self._destroyed:
            self._lib.hvdtpu_step_end(self._session, step_id)

    def set_tuned_params(self, cycle_time_ms: Optional[float] = None,
                         fusion_threshold_bytes: Optional[int] = None,
                         cache_enabled: Optional[bool] = None,
                         low_latency_threshold_bytes: Optional[int] = None,
                         express_lane: Optional[bool] = None,
                         ring_threshold_bytes: Optional[int] = None,
                         hierarchical: Optional[bool] = None,
                         small_tensor_algo: Optional[str] = None):
        """Push engine knobs at runtime (the frontend autotuner's engine
        hook). The record is staged and adopted by every rank at the same
        coordination-cycle boundary via the parameter-sync broadcast —
        requires ``HOROVOD_TUNE=1`` on multi-rank sessions (single-rank
        sessions apply on the next cycle unconditionally). ``None`` keeps
        the current value. The data-plane routing knobs
        (``ring_threshold_bytes``, ``hierarchical``,
        ``small_tensor_algo`` in {"star", "rd"}) ride the same fence, so
        the tuner can search them without ever splitting ranks across
        algorithms. Raises on a session that cannot sync."""
        rc = self._lib.hvdtpu_set_tuned_params(
            self._session,
            -1.0 if cycle_time_ms is None else float(cycle_time_ms),
            -1 if fusion_threshold_bytes is None
            else int(fusion_threshold_bytes),
            -1 if cache_enabled is None else int(bool(cache_enabled)),
            -1 if low_latency_threshold_bytes is None
            else int(low_latency_threshold_bytes),
            -1 if express_lane is None else int(bool(express_lane)),
            -1 if ring_threshold_bytes is None
            else int(ring_threshold_bytes),
            -1 if hierarchical is None else int(bool(hierarchical)),
            -1 if small_tensor_algo is None
            else SMALL_TENSOR_ALGOS[small_tensor_algo])
        if rc != 0:
            raise HorovodInternalError(
                self._lib.hvdtpu_last_error().decode())

    def tuned_params(self) -> dict:
        """The currently applied engine knobs: ``{"cycle_time_ms",
        "fusion_threshold_bytes", "low_latency_threshold_bytes",
        "ring_threshold_bytes", "cache_enabled", "tuning_active",
        "express_lane", "hierarchical", "small_tensor_algo"}``. Reflects
        a :meth:`set_tuned_params` push only after the next coordination
        cycle applied/broadcast it."""
        return self._json_call(self._lib.hvdtpu_get_tuned_params) or {}

    # -- data plane hookup --------------------------------------------------

    def set_execute_callback(self, fn: Callable[[dict], int]):
        """Register the data-plane executor. ``fn`` receives the fused
        response dict {type, names, dtypes, shapes, sizes, joined_ranks,
        reduce_op, root_rank, prescale, postscale} and returns 0 on
        success."""

        def c_callback(json_bytes, _user):
            try:
                return int(fn(json.loads(json_bytes.decode())))
            except Exception:
                import traceback
                traceback.print_exc()
                return 1

        self._cb_ref = _EXECUTE_CB(c_callback)
        self._lib.hvdtpu_set_execute_callback(self._session, self._cb_ref,
                                              None)

    # -- async op surface ---------------------------------------------------

    def enqueue(self, name: str, op_type: int, dtype: str,
                shape: Sequence[int], root_rank: int = 0,
                reduce_op: int = 0, prescale_factor: float = 1.0,
                postscale_factor: float = 1.0, group_id: int = -1,
                group_size: int = 0,
                splits: Optional[Sequence[int]] = None) -> int:
        dims = (ctypes.c_int64 * len(shape))(*shape)
        csplits = None
        nsplits = 0
        if splits:
            csplits = (ctypes.c_int64 * len(splits))(*splits)
            nsplits = len(splits)
        handle = ctypes.c_int64(-1)
        rc = self._lib.hvdtpu_enqueue(
            self._session, name.encode(), op_type, DTYPE_IDS[dtype], dims,
            len(shape), root_rank, reduce_op, prescale_factor,
            postscale_factor, group_id, group_size, csplits, nsplits,
            ctypes.byref(handle))
        if rc != 0:
            raise HorovodInternalError(
                self._lib.hvdtpu_last_error().decode())
        return handle.value

    def join(self) -> int:
        handle = ctypes.c_int64(-1)
        rc = self._lib.hvdtpu_join(self._session, ctypes.byref(handle))
        if rc != 0:
            raise HorovodInternalError(
                self._lib.hvdtpu_last_error().decode())
        return handle.value

    def last_joined_rank(self) -> int:
        """Last rank to join in the most recent completed join epoch
        (reference: torch/mpi_ops.py:846+ return contract)."""
        return self._lib.hvdtpu_last_joined_rank(self._session)

    def poll(self, handle: int):
        buf = ctypes.create_string_buffer(4096)
        rc = self._lib.hvdtpu_poll(self._session, handle, buf, len(buf))
        if rc < 0:
            raise HorovodInternalError(
                self._lib.hvdtpu_last_error().decode())
        return rc == 1, buf.value.decode()

    def wait(self, handle: int, timeout: float = 0.0):
        """Blocks until the op completes; raises HorovodInternalError on
        coordination/validation/data-plane failure, WaitTimeout when
        ``timeout`` elapses first (the op is still pending and the handle
        stays live — wait again)."""
        buf = ctypes.create_string_buffer(8192)
        rc = self._lib.hvdtpu_wait(self._session, handle, timeout, buf,
                                   len(buf))
        if rc == 5:  # StatusType::IN_PROGRESS
            from horovod_tpu.common.exceptions import WaitTimeout
            raise WaitTimeout(buf.value.decode() or "wait timed out")
        if rc == 6:  # StatusType::CORRUPTED — CRC-detected wire corruption
            from horovod_tpu.common.exceptions import HorovodCorruptedError
            raise HorovodCorruptedError(buf.value.decode() or
                                        "corrupted frame")
        if rc != 0:
            raise HorovodInternalError(buf.value.decode() or
                                       "collective failed")

    # -- timeline -----------------------------------------------------------

    def start_timeline(self, path: str, mark_cycles: bool = False):
        self._lib.hvdtpu_start_timeline(self._session, path.encode(),
                                        1 if mark_cycles else 0)

    def stop_timeline(self):
        self._lib.hvdtpu_stop_timeline(self._session)

    def timeline_activity_start(self, name: str, activity: str):
        """Open a nested activity span on the tensor's timeline lane
        (no-op unless a timeline is active)."""
        self._lib.hvdtpu_timeline_activity_start(
            self._session, name.encode(), activity.encode())

    def timeline_activity_end(self, name: str):
        self._lib.hvdtpu_timeline_activity_end(self._session, name.encode())
